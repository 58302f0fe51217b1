"""``serve_prefill_token_share``: prompt tokens the window's steps processed
over all tokens they processed (the engine's counters; tokens served from the
prefix cache are not processed and not counted)."""


def read(run):
    c = run["counts"]
    total = c["prefill_tokens"] + c["generated_tokens"]
    if total <= 0:
        return None
    return 100.0 * c["prefill_tokens"] / total
