"""Aggregators: the projection refit's share of its roofline. The least time
the chip could take for the refits' value+gradient passes (costs_kron.py:
each real active row's features and per-row vectors and each entity's
factors read once a pass, 4 k d operations a row, against peaks.json) over
the device seconds inside the runs of `jit_fe_solve` that the factored
coordinate's update called, per traced fit (coordinate_reduce_fe.py). The
passes are the program's own count (`mf_projection_passes.fit`); the line
search's trials read cached margins and are part of the seconds, not of the
work."""
from benchmark import coordinate_reduce_fe, costs, costs_kron
from benchmark.run import load_module

META = {"name": "mf_kron_roofline.fit", "unit": "%",
        "layer": "Aggregators (kernels)", "moves": "fit_examples_per_s"}


def read(record):
    built, peak = record["built"], record["peak"]
    stats = (built.get("coordinates") or {}).get("perUserMF", {}).get(
        "mf_build")
    passes = load_module("layer_metrics", "mf_projection_passes.fit").read(
        record)
    if not (peak and stats and passes):
        return None
    seconds = coordinate_reduce_fe.fe_solve_seconds(record, "perUserMF")
    if not seconds:
        return None
    width = built["per_user_width"]
    least = passes * costs.roofline_seconds(
        costs_kron.kron_value_grad_pass_bytes(
            stats["real_rows"], stats["entities"], width,
            stats["latent_dim"], built["itemsize"]),
        costs_kron.kron_value_grad_pass_flops(
            stats["real_rows"], width, stats["latent_dim"]), peak)
    return 100.0 * least / seconds
