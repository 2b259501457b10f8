"""Aggregators: the sparse fixed-effect solve's share of its roofline. The
least time the chip could take for the solve's value+gradient passes
(costs_sparse.py: every non-zero's index and value and the per-row vectors
read once a pass, against peaks.json; the bytes bound it) over the device
seconds inside the runs of `jit_fe_solve` per traced fit (span_reduce.py).
The passes are the program's own count (`fg_count`, through the builder's
record); the line search's trials read cached margins and are part of the
seconds, not of the work."""
from benchmark import costs, costs_sparse, span_reduce

META = {"name": "fe_sparse_roofline.fit", "unit": "%",
        "layer": "Aggregators (kernels)", "moves": "fit_examples_per_s"}


def read(record):
    built, peak = record["built"], record["peak"]
    passes = [f["record"]["passes"] for f in record["samples"].get("fits", [])
              if f["record"].get("passes")]
    if not (peak and passes and "nnz" in built):
        return None
    seconds = span_reduce.median_per_fit(
        record, lambda fit: span_reduce.solve_seconds(
            fit, span_reduce.FE_SOLVE))
    if not seconds:
        return None
    least = passes[-1] * costs.roofline_seconds(
        costs_sparse.sparse_value_grad_pass_bytes(
            built["train_rows"], built["nnz"], built["itemsize"]),
        costs_sparse.sparse_value_grad_pass_flops(built["nnz"]), peak)
    return 100.0 * least / seconds
