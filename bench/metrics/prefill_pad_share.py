"""Scheduler: share of the tokens the traced admissions computed that were
padding, from the program's own `swat.admit` spans: 1 - sum of
prompt_tokens / sum of padded_tokens, in %. None when the program records
no such spans."""
import spans


def read(run):
    admits = spans.program_spans(run, "swat.admit")
    if not admits:
        return None
    padded = sum(c["padded_tokens"] for *_, c in admits)
    return 100.0 * (1.0 - sum(c["prompt_tokens"] for *_, c in admits) / padded)
