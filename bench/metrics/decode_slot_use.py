"""Decode step: tokens the traced decode blocks emitted over the slot-steps
they computed (every slot computes every step, live or not), from the
program's own spans: sum of `emitted` over `swat.decode.retire` / sum of
n x slots over `swat.decode`, in %. None when the program records no such
spans."""
import spans


def read(run):
    blocks = spans.program_spans(run, "swat.decode")
    retired = spans.program_spans(run, "swat.decode.retire")
    if not blocks or not retired:
        return None
    slot_steps = sum(c["n"] * c["slots"] for *_, c in blocks)
    return 100.0 * sum(c["emitted"] for *_, c in retired) / slot_steps
