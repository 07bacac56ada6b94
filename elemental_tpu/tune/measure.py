"""Measurement engine: time candidate configs on the real backend and
record winners in the persistent cache.

Every candidate runs IN ONE PROCESS on the same devices, timings are
min-of-reps with the host round-trip latency subtracted and each variant
is bracketed by a matmul roofline measurement so chip weather is factored
out of the comparison.  Inputs are regenerated (untimed) per rep because
the jitted steps donate their operand.

``search()`` is the CLI entry (``python -m perf.tune search``): it
pre-ranks the candidate space with the analytic cost model (cheap), times
the top slice, and atomically persists the winner as a
``tuning_cache/v1`` entry that every later ``'auto'`` resolution on the
same (op, shape-bucket, dtype, grid, backend) key picks up first.
"""
from __future__ import annotations

import dataclasses
import time

from . import cache as _cache
from .cost_model import op_flops
from .policy import explain


@dataclasses.dataclass
class Measured:
    """One timed candidate."""
    config: dict
    seconds: float
    tflops: float
    roofline_tflops: float

    def to_doc(self) -> dict:
        return {"config": dict(self.config), "seconds": self.seconds,
                "tflops": self.tflops,
                "roofline_tflops": self.roofline_tflops}


def _latency():
    import jax
    import jax.numpy as jnp
    tiny = jax.jit(lambda x: x + 1.0)
    t = jnp.zeros(())
    float(tiny(t))
    return min(_rep(lambda: float(tiny(t))) for _ in range(3))


def _rep(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _roofline(lat: float, n: int = 2048):
    import jax
    import jax.numpy as jnp
    R = jax.random.normal(jax.random.PRNGKey(9), (n, n), jnp.float32)
    mm = jax.jit(lambda x: jnp.matmul(x, x,
                                      precision=jax.lax.Precision.HIGHEST))
    float(mm(R)[0, 0])
    dt = max(min(_rep(lambda: float(mm(R)[0, 0])) for _ in range(3)) - lat,
             1e-9)
    return 2 * n ** 3 / dt / 1e12


def _builders(op: str, dims, grid, dtype):
    """(make_input, step_factory) for one op; step_factory(config) returns
    a donated jitted step whose output fences the whole computation."""
    import jax
    import jax.numpy as jnp
    import elemental_tpu as el

    HI = jax.lax.Precision.HIGHEST

    def dm(a, m, n):
        return el.DistMatrix(a, (m, n), el.MC, el.MR, 0, 0, grid)

    if op == "cholesky":
        n = dims[0]

        @jax.jit
        def gen():
            G = jax.random.normal(jax.random.PRNGKey(0), (n, n), dtype)
            return jnp.matmul(G, G.T) / n + n * jnp.eye(n, dtype=dtype)

        def make():
            return dm(gen(), n, n)

        def factory(cfg):
            return jax.jit(lambda a: el.cholesky(
                a, nb=cfg.get("nb"), lookahead=cfg.get("lookahead", True),
                crossover=cfg.get("crossover"),
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"),
                precision=HI).local,
                donate_argnums=0)
        return make, factory
    if op == "lu":
        m, n = dims[0], dims[-1]
        gen = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(1),
                                                (m, n), dtype))

        def make():
            return dm(gen(), m, n)

        def factory(cfg):
            return jax.jit(lambda a: tuple(el.lu(
                a, nb=cfg.get("nb"), lookahead=cfg.get("lookahead", True),
                crossover=cfg.get("crossover"),
                panel=cfg.get("panel") or "classic",
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"), precision=HI)),
                donate_argnums=0)
        return make, factory
    if op == "qr":
        m, n = dims[0], dims[-1]
        gen = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(2),
                                                (m, n), dtype))

        def make():
            return dm(gen(), m, n)

        def factory(cfg):
            return jax.jit(lambda a: tuple(el.qr(
                a, nb=cfg.get("nb"), panel=cfg.get("panel") or "classic",
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"), precision=HI)),
                donate_argnums=0)
        return make, factory
    if op == "trsm":
        m, n = dims[0], dims[-1]

        @jax.jit
        def gen():
            a = jax.random.normal(jax.random.PRNGKey(3), (m, m), dtype)
            a = jnp.tril(a) + m * jnp.eye(m, dtype=dtype)   # well-conditioned
            b = jax.random.normal(jax.random.PRNGKey(4), (m, n), dtype)
            return a, b

        def make():
            a, b = gen()
            return (dm(a, m, m), dm(b, m, n))

        def factory(cfg):
            return jax.jit(lambda ab: el.trsm(
                "L", "L", "N", ab[0], ab[1], nb=cfg.get("nb"),
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"),
                precision=HI).local,
                donate_argnums=0)
        return make, factory
    if op == "herk":
        m, k = dims[0], dims[-1]
        gen = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(5),
                                                (m, k), dtype))

        def make():
            return dm(gen(), m, k)

        def factory(cfg):
            return jax.jit(lambda a: el.herk(
                "L", a, nb=cfg.get("nb"),
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"),
                precision=HI).local,
                donate_argnums=0)
        return make, factory
    if op == "gemm":
        m, k, n = dims

        @jax.jit
        def gen():
            a = jax.random.normal(jax.random.PRNGKey(6), (m, k), dtype)
            b = jax.random.normal(jax.random.PRNGKey(7), (k, n), dtype)
            return a, b

        def make():
            a, b = gen()
            return (dm(a, m, k), dm(b, k, n))

        def factory(cfg):
            return jax.jit(lambda ab: el.gemm(
                ab[0], ab[1], alg=cfg.get("alg", "auto"),
                nb=cfg.get("nb"),
                comm_precision=cfg.get("comm_precision"),
                redist_path=cfg.get("redist_path"),
                precision=HI).local,
                donate_argnums=0)
        return make, factory
    raise KeyError(f"no measurement builder for op {op!r}")


def measure_candidates(op: str, dims, grid, dtype, candidates,
                       reps: int = 3, verbose: bool = False) -> list:
    """Time each candidate config (roofline-bracketed); best-first list."""
    import jax
    flops = op_flops(op, dims)
    make, factory = _builders(op, dims, grid, dtype)
    lat = _latency()
    out = []
    for cfg in candidates:
        step = factory(cfg)
        first = step(make())                       # compile + warm
        jax.block_until_ready(first)
        del first
        r0 = _roofline(lat)
        times = []
        for _ in range(reps):
            A = make()
            jax.block_until_ready(A)
            t0 = time.perf_counter()
            o = step(A)
            jax.block_until_ready(o)
            times.append(time.perf_counter() - t0)
        del o
        r1 = _roofline(lat)
        dt = max(min(times) - lat, 1e-9)
        m = Measured(config=dict(cfg), seconds=dt, tflops=flops / dt / 1e12,
                     roofline_tflops=0.5 * (r0 + r1))
        out.append(m)
        if verbose:
            print(f"  {str(cfg):60s} {dt * 1e3:9.2f} ms "
                  f"{m.tflops:7.3f} TFLOP/s (roof {m.roofline_tflops:.2f})",
                  flush=True)
        del step
    out.sort(key=lambda m: m.seconds)
    return out


def search(op: str, dims, grid, dtype, requested: dict | None = None,
           top: int = 8, reps: int = 3, write_cache: bool = True,
           verbose: bool = False):
    """Cost-model-pre-ranked measurement sweep; persists the winner.

    Returns ``(winner: Measured, all_measured: list, key)``.  The cache
    entry records the measured config with ``source='measured'`` so
    subsequent ``'auto'`` resolutions on this key skip the cost model.
    """
    ctx, scored = explain(op, gshape=dims, dtype=dtype, grid=grid,
                          requested=requested)
    cands = [b.config for b in scored[:max(1, top)]]
    if verbose:
        print(f"{op} {tuple(dims)} on {ctx.grid_shape[0]}x"
              f"{ctx.grid_shape[1]} {ctx.backend}: measuring "
              f"{len(cands)}/{len(scored)} cost-ranked candidates",
              flush=True)
    measured = measure_candidates(op, dims, grid, dtype, cands, reps=reps,
                                  verbose=verbose)
    winner = measured[0]
    key = _cache.make_key(op, ctx.dims, ctx.dtype, ctx.grid_shape,
                          ctx.backend)
    if write_cache:
        _cache.save(key, winner.config, source="measured",
                    metric={"seconds": winner.seconds,
                            "tflops": winner.tflops,
                            "roofline_tflops": winner.roofline_tflops})
        from .policy import clear_memo
        clear_memo()                       # new winner visible immediately
    return winner, measured, key


__all__ = ["Measured", "measure_candidates", "search"]
