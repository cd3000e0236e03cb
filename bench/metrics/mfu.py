"""Model FLOPs utilization of the whole step: forward + backward FLOPs per
sample (the configuration's counts module, bench/counts.py by default) x
the traced run's samples per second, over the chip's peak bf16 FLOP/s, in
%."""


def read(run):
    steps = len(run.window.steps)
    if not steps or not run.peaks:
        return None
    rate = steps * run.batch / run.window.length
    return (100.0 * run.spec.counts.model_flops_per_sample(run.sizes) * rate
            / run.peaks["bf16_flops_per_s"])
