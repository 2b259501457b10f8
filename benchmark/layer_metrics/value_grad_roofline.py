"""Aggregators: the fused value+gradient pass's share of its roofline.

The least time the chip could take for one solve's passes (costs.py: the
matrix and the labels read once per pass, against peaks.json) over the
device busy seconds per solve, from the trace's marks."""
from benchmark import costs

META = {"name": "value_grad_roofline", "unit": "%",
        "layer": "Aggregators (kernels)", "moves": "fit_examples_per_s"}


def read(record):
    trace, peak, built = record["trace"], record["peak"], record["built"]
    fits = [f["record"] for f in record["samples"].get("fits", [])]
    if not (trace and peak and fits and "passes" in fits[-1]
            and "width" in built):
        return None
    marks = [m for m in trace["marks"] if m["busy_s"] > 0]
    if not marks:
        return None
    busy_per_fit = sum(m["busy_s"] for m in marks) / len(marks)
    rows, width = built["train_rows"], built["width"]
    least = fits[-1]["passes"] * costs.roofline_seconds(
        costs.value_grad_pass_bytes(rows, width, built["itemsize"]),
        costs.value_grad_pass_flops(rows, width), peak)
    return 100.0 * least / busy_per_fit
