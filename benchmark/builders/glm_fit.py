"""Role "fit" for one dense GLM: a1a's shape replicated row-wise, made on the
device from the seed, solved by the program's `solve` exactly as `bench.py`'s
`time_glm_solve` calls it, and certified in float64 on the host.
"""
from __future__ import annotations

import numpy as np

from benchmark import reference


def make_on_device(seed, chunks, chunk_rows, width, density, truth_scale,
                   truth_seed):
    """(x [chunks*chunk_rows, width] float32, y) in ONE jitted call. Chunk c
    depends on (seed, c) alone, so raising `replicas` keeps the rows there.
    The planted truth comes from `truth_seed`, which the configuration fixes:
    with it every seed poses a problem of the same difficulty."""
    import jax
    import jax.numpy as jnp

    def make(key):
        w = truth_scale * jax.random.normal(
            jax.random.PRNGKey(truth_seed), (width,))

        def chunk(c):
            kx, ky = jax.random.split(jax.random.fold_in(key, c))
            x = (jax.random.uniform(kx, (chunk_rows, width))
                 < density).astype(jnp.float32)
            x = x.at[:, -1].set(1.0)
            z = jnp.sum(x * w, axis=1)           # float32, no MXU rounding
            y = (jax.random.uniform(ky, (chunk_rows,))
                 < jax.nn.sigmoid(z)).astype(jnp.float32)
            return x, y

        xs, ys = jax.lax.map(chunk, jnp.arange(chunks))
        return xs.reshape(-1, width), ys.reshape(-1)

    # any whole number up to a little over 2**31 is a seed
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.jit(make)(key)


class GlmFit:
    def __init__(self, config, seed, chips):
        import jax
        import jax.numpy as jnp
        from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
        from photon_ml_tpu.optim import (OptimizerConfig,
                                         RegularizationContext,
                                         RegularizationType, solve)

        p = config["params"]
        self.chunk_rows = config["rows_per_replica"] * p["chunk_replicas"]
        self.chunks = config["replicas"] // p["chunk_replicas"]
        assert self.chunks * p["chunk_replicas"] == config["replicas"]
        self.width = config["width"]
        x, y = make_on_device(seed, self.chunks, self.chunk_rows, self.width,
                              config["density"], p["truth_scale"],
                              p["truth_seed"])
        self.train_rows = int(x.shape[0])
        self.lam = float(p["l2"])
        self.obj = GLMObjective(TASK_LOSSES["logistic_regression"], x, y)
        opt = OptimizerConfig(max_iterations=p["max_iterations"],
                              tolerance=p["tolerance"])
        reg = RegularizationContext(RegularizationType.L2)
        self.run = jax.jit(lambda o, x0, lam: solve(o, x0, opt, reg, lam))
        self.x0 = jnp.zeros((self.width,), jnp.float32)
        self.lam_j = jnp.asarray(self.lam, jnp.float32)
        self.last = None
        self.info = {"train_rows": self.train_rows, "width": self.width,
                     "itemsize": int(x.dtype.itemsize)}

    def fit(self):
        import jax
        self.last = jax.block_until_ready(
            self.run(self.obj, self.x0, self.lam_j))
        return self.last

    def record(self, result) -> dict:
        passes = int(result.fg_count if result.fg_count is not None else 0)
        passes += int(result.hv_count if result.hv_count is not None else 0)
        return {"w": np.asarray(result.x), "value": float(result.value),
                "iterations": int(result.iterations),
                "reason": int(result.reason), "passes": passes}

    def check(self, records) -> dict:
        """Every fit returned the same finite w, and f(w) is within 1e-4
        relative of the float64 optimum (reference.certify_logistic).

        The matrix comes back to the host as packed bits, 16 bytes a row and
        not 496, after the device has confirmed that it holds only 0 and 1.
        The Hessian that steers the Newton step is multiplied on the device;
        f and grad f, which decide, are float64 NumPy on the host."""
        import time

        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        x, y, m = self.obj.x, self.obj.labels, self.chunk_rows
        binary = bool(jnp.all((x == 0) | (x == 1)))
        packed = np.asarray(jnp.packbits(x != 0, axis=1))
        y_host = np.asarray(y)
        x_chunks = [reference.PackedBits(packed[c * m:(c + 1) * m],
                                         self.width)
                    for c in range(self.chunks)]
        y_chunks = [y_host[c * m:(c + 1) * m] for c in range(self.chunks)]
        t1 = time.perf_counter()

        @jax.jit
        def hessian(v):
            def one(xc):            # chunk by chunk keeps the temporaries small
                p = jax.nn.sigmoid(jnp.sum(xc * v, axis=1))
                return jnp.einsum("nd,n,ne->de", xc, p * (1.0 - p), xc,
                                  precision=jax.lax.Precision.HIGHEST)
            return jax.lax.map(one, x.reshape(self.chunks, m,
                                              self.width)).sum(0)

        def hessian_on_device(v):
            return (np.asarray(hessian(jnp.asarray(v, jnp.float32)),
                               np.float64) + self.lam * np.eye(self.width))

        w = records[-1]["w"]
        out = reference.certify_logistic(x_chunks, y_chunks, w, self.lam,
                                         1e-4, hessian=hessian_on_device)
        out["seconds"] = {"pull": t1 - t0,
                          "certify": time.perf_counter() - t1}
        out["value_reported"] = records[-1]["value"]
        out["binary"] = binary
        out["fits_agree"] = all(reference.same_to(r["w"], records[0]["w"],
                                                  1e-6) for r in records)
        out["finite"] = bool(np.isfinite(w).all())
        out["ok"] = bool(out["ok"] and binary and out["fits_agree"]
                         and out["finite"])
        return out


def build(config, seed, chips):
    return GlmFit(config, seed, chips)
