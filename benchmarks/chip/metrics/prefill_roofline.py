"""prefill_roofline: the least time the chip could take for the probe
prefill calls made while the profiler ran (the architecture module's
``prefill_flops`` and ``prefill_bytes`` of each call's logical rows and
lengths at the model's widths, against peaks.json), over the device time of
the prefill program executions in the trace, in percent."""
from chipbench import flops


def read(run):
    tr, peak = run.state.trace, run.peak
    if tr is None or peak is None or not tr["module_count"]:
        return None
    need = 0.0
    for c in run.state.calls:
        if c.traced and c.live_rows:
            t, _ = flops.roofline_seconds(
                run.arch.prefill_flops(run.dims, c.live_rows, c.new,
                                       c.cached),
                run.arch.prefill_bytes(run.dims, c.live_rows, c.new,
                                       c.cached, c.reserve), peak)
            need += t
    return 100.0 * need / tr["module_s"] if need else None
