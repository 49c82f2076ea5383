"""Share of the prompt tokens admitted in the window that the radix
prefix cache served: the program's per-request reuse (the amount its
admission adds to pool.stats["prefill_tokens_saved"]) over prompt
tokens, in percent."""


def read(run):
    recs = run.admitted_in_window()
    total = sum(r.prompt_len for r in recs)
    return 100.0 * sum(r.reuse for r in recs) / total if total else None
