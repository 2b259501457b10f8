"""Device: 1 - union of device-op intervals over the traced window of whole
fits (trace_reduce.py)."""
META = {"name": "device_idle_share.fit", "unit": "%", "layer": "Device",
        "moves": "fit_examples_per_s"}


def read(record):
    trace = record["trace"]
    return None if not trace else 100.0 * trace["idle_share"]
