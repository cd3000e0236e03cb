"""Model FLOPs utilization of the whole step: forward + backward FLOPs per
sample (bench/counts.py) x the traced run's samples per second, over the
chip's peak bf16 FLOP/s, in %."""


def read(run):
    from bench import counts
    steps = len(run.window.steps)
    if not steps or not run.peaks:
        return None
    rate = steps * run.batch / run.window.length
    return (100.0 * counts.model_flops_per_sample(run.sizes) * rate
            / run.peaks["bf16_flops_per_s"])
