"""live_token_share: the non-PAD tokens among the tokens the prefill
programs ran inside the window (ServeStats prefill_live_tokens over
prefill_tokens, deltas); nothing where the program does not count them."""


def read(run):
    a, b = run.state.stats_open, run.state.stats_close
    if getattr(b, "prefill_live_tokens", None) is None:
        return None
    total = b.prefill_tokens - a.prefill_tokens
    return (b.prefill_live_tokens - a.prefill_live_tokens) / total \
        if total else None
