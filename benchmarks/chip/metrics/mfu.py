"""mfu: model operations of the live, unpadded probe tokens the prefill
programs ran inside the window, over the window's length times the chip's
peak (peaks.json), in percent.  The operations are the architecture
module's ``model_flops``."""


def read(run):
    s, peak = run.state, run.peak
    if peak is None:
        return None
    ops = sum(run.arch.model_flops(run.dims, c.live_tokens, c.live_rows,
                                   c.attn_pairs)
              for c in s.calls if s.t_open <= c.t <= s.t_close)
    if not ops:
        return None
    return 100.0 * ops / ((s.t_close - s.t_open) * peak["bf16_flops_per_s"])
