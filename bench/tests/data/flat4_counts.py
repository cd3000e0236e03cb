"""Counts of the ``flat4`` test configuration: its MLPs and bag sums."""


def _flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:], strict=True))


def model_flops_per_sample(sizes: dict) -> float:
    """Forward + backward (twice the forward) of both MLPs and the bags."""
    top = ((len(sizes["rows"]) + 1) * sizes["embed_dim"], *sizes["top_mlp"])
    fwd = (_flops(sizes["bottom_mlp"]) + _flops(top)
           + (sum(sizes["pooling"]) - len(sizes["rows"])) * sizes["embed_dim"])
    return 3.0 * fwd


def step_min_seconds(sizes: dict, unique_rows: int, peaks: dict):
    """(least seconds, bound): the FLOPs, or each touched row read and
    written once in bf16."""
    t_flops = sizes["batch"] * model_flops_per_sample(sizes) / peaks[
        "bf16_flops_per_s"]
    t_bytes = 2 * unique_rows * sizes["embed_dim"] * 2 / peaks[
        "hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
