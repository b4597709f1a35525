"""Decode step, full-attention layers: the cache rows their queries may see
over the rows their attention reads, from the program's own `swat.decode`
spans: sum of `full_rows_needed` / sum of `full_rows_read`, in %. None when
the program records no such spans or counts no full-layer rows."""
import spans


def read(run):
    blocks = spans.program_spans(run, "swat.decode")
    if not blocks:
        return None
    read_rows = sum(c.get("full_rows_read", 0) for *_, c in blocks)
    if read_rows <= 0:
        return None
    return 100.0 * sum(c.get("full_rows_needed", 0) for *_, c in blocks) / read_rows
