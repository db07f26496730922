"""Share of the prompt tokens admitted in the window that the paged
cache served from warm pages instead of prefilling them: reused /
(reused + prefilled), from ``stats()["cache"]``."""


def read(run):
    c = run.stats["cache"]
    total = c["reused_prefill_tokens"] + c["suffix_prefill_tokens"]
    return 100.0 * c["reused_prefill_tokens"] / total if total else None
