"""Compile: XLA backend-compile seconds during set-up (the program's
CompileTimeTracker, jax.monitoring). Near 0 once the persistent cache holds
every program."""
META = {"name": "compile_s", "unit": "s", "layer": "Compile",
        "moves": "setup_s"}


def read(record):
    return record["compile"]["setup_seconds"]
