"""Compile: the seconds of the process that JAX spent tracing Python to
jaxprs and lowering them to MLIR, from the program's always-on counters
`jax.trace_s` and `jax.lower_s` (utils/jax_cache.py; each second counted
once, nested traces taken out of their parents). No cache saves them. The
window compiles nothing (`compiles_in_window` is 0), so the process totals
after it are set-up's, and a little more: this is read after the run's check
of its results, whose own few programs trace for 0.2 to 0.4 s on the chip."""
META = {"name": "trace_lower_s", "unit": "s", "layer": "Compile",
        "moves": "setup_s"}


def read(record):
    if not record.get("trace"):
        return None
    from photon_ml_tpu.telemetry import default_registry
    registry = default_registry()
    if not {"jax.trace_s", "jax.lower_s"} <= set(registry.names()):
        return None             # an older commit: no such counters
    return (registry.counter("jax.trace_s").value
            + registry.counter("jax.lower_s").value)
