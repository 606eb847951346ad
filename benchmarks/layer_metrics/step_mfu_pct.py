"""Jitted step, whole: the share of the chips' peak that the model's own
forward + backward operations come to. The configuration's analytic
operations per sample x the samples per second of the traced stretch (whole
``train_step`` executions over the trace's window), over chips x the peak of
the cell's compute type. What the optimizer, a recomputation or a layout
change costs is not counted as useful work."""

from benchmarks import trace_reduce


def read(ctx):
    red, cell = ctx["trace"], ctx["cell"]
    steps = min(len(trace_reduce.steps_in_window(red, d)) for d in red.devices)
    if not steps or red.window_s <= 0:
        return None
    samples_per_s = steps * cell.traffic["batch"] / red.window_s
    flops = ctx["module"].train_flops_per_sample(cell.config, cell.traffic)
    peak = ctx["peaks"]["flops_per_s"][cell.config["compute_dtype"]]
    return 100.0 * flops * samples_per_s / (ctx["chips"] * peak)
