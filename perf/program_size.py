"""What a compiled program costs the persistent compile cache, without a chip.

``heig.2x2.b2b``'s executable is the largest a cell compiles, and the chip
machine's cache takes no entry over ``CACHE_ENTRY_LIMIT`` bytes: a program
over it is compiled by EVERY run (``setup_s`` 794 s for 92 s warm, PERF.md
6, PR 51).  jax stores ``compress_executable(serialize(compiled))``; on the
rehearsal's executable that gave the chip's entry to 0.02 %.

    python -m perf.program_size eig --n 16384            # ten minutes, 20 GB
    python -m perf.program_size eig --n 16384 --stage dc # the stages alone
    python -m perf.program_size svd --n 16384            # a quarter of an hour
    python -m perf.program_size drivers                  # CPU, seconds

``eig`` compiles the donated ``jit(herm_eig)`` (nb 256, float32) for a
described ``v5e:2x2`` and prints the entry's bytes beside the plan, the
lines and the trace-time counters; ``svd`` the donated ``jit(el.svd)`` of
``svd.1x1.b2b`` (no ``nb``) for ONE described v5e chip, the same way.
``drivers`` prints a hash of the
stripped optimized HLO (CPU backend, n = 256, one device and 2x2; metadata
out, instructions renamed in order) of every driver a cell of the benchmark
compiles, ``least_squares`` on 2x2 by both routes.  ``--root <checkout>`` imports
``elemental_tpu`` from another tree: run both on two trees to see which
programs a change moved (equal hashes: the program is the other tree's)
and by how much.  ONE n = 16384 rehearsal at a time (two at most, with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` in the shell): three took 83 GiB.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
import time

#: the chip machine's limit on one entry of its compile cache (192 MiB)
CACHE_ENTRY_LIMIT = 192 << 20

STAGES = ("whole", "tridiag", "dc", "applyq")


def entry_bytes(compiled):
    """``(serialized, cache entry)`` bytes of a compiled executable: what
    ``serialize_executable`` gives and what jax's compile cache stores."""
    from jax._src import compilation_cache
    from jax.experimental.serialize_executable import serialize
    payload = serialize(compiled)[0]
    return len(payload), len(compilation_cache.compress_executable(payload))


def _eig(args):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec
    jax.config.update("jax_enable_compilation_cache", False)
    import elemental_tpu as el
    from elemental_tpu.core.distmatrix import DistMatrix
    from elemental_tpu.lapack.condense import (apply_q_herm_tridiag,
                                               hermitian_tridiag)
    from elemental_tpu.lapack.tridiag_eig import tridiag_eig

    n, nb, hi = args.n, 256, jax.lax.Precision.HIGHEST
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    grid = el.Grid(list(topo.devices))

    def matrix():
        meta = DistMatrix(None, (n, n), el.MC, el.MR, 0, 0, grid)
        return meta.with_local(jax.ShapeDtypeStruct(
            (n, n), jnp.float32, sharding=grid.sharding(meta.spec)))

    def vector(k):
        return jax.ShapeDtypeStruct(
            (k,), jnp.float32,
            sharding=NamedSharding(grid.mesh, PartitionSpec()))

    fn, operands, donate = {
        "whole": (lambda a: el.herm_eig(a, nb=nb), (matrix(),), (0,)),
        "tridiag": (lambda a: hermitian_tridiag(a, "L", nb=nb, precision=hi),
                    (matrix(),), (0,)),
        "dc": (lambda d, e: tridiag_eig(d, e, grid=grid, precision=hi),
               (vector(n), vector(n - 1)), ()),
        "applyq": (lambda ap, tau, z: apply_q_herm_tridiag(
            ap, tau, z, orient="N", nb=nb, precision=hi),
            (matrix(), vector(n - 1), matrix()), (2,)),
    }[args.stage]
    return _rehearse(jax.jit(fn, donate_argnums=donate), operands, args.hlo,
                     ("gemm_route", "dc_merge"),
                     stage=args.stage, n=n, grid=[grid.height, grid.width])


def _rehearse(jitted, operands, hlo, counters, **head):
    """Lower and compile ``jitted`` for the described chip, print one JSON
    line (``head``, the seconds, the plan, the lines, the entry's bytes,
    the named trace-time counters) and return 1 if the entry is over the
    cache's limit."""
    from elemental_tpu import obs
    start = time.time()
    with obs.metrics_scope() as reg:
        lowered = jitted.lower(*operands)
        lowered_at = time.time()
        compiled = lowered.compile()
    compiled_at = time.time()
    text = compiled.as_text()
    if hlo:
        with open(hlo, "w") as out:
            out.write(text)
    mem = compiled.memory_analysis()
    parts = {"argument": mem.argument_size_in_bytes,
             "output": mem.output_size_in_bytes,
             "temp": mem.temp_size_in_bytes,
             "alias": mem.alias_size_in_bytes}
    serialized, entry = entry_bytes(compiled)
    print(json.dumps({
        **head,
        "trace_lower_s": round(lowered_at - start, 1),
        "compile_s": round(compiled_at - lowered_at, 1),
        "plan_bytes": (parts["argument"] + parts["output"] + parts["temp"]
                       - parts["alias"]),
        "plan_parts": parts,
        "hlo_lines": text.count("\n") + 1,
        "callbacks": text.count("custom_call_target=\"xla_python"),
        "serialized_bytes": serialized, "cache_entry_bytes": entry,
        "cache_entry_limit": CACHE_ENTRY_LIMIT,
        "fits": entry <= CACHE_ENTRY_LIMIT,
        "counters": {name: {",".join(f"{k}={v}" for k, v in labels): count
                            for (_n, labels), count
                            in reg.counters(name).items()}
                     for name in counters}}), flush=True)
    return 0 if entry <= CACHE_ENTRY_LIMIT else 1


def _svd(args):
    """The donated ``jit(el.svd)`` of ``svd.1x1.b2b`` (square float32, ONE
    described v5e chip, no ``nb``: the blocks are the driver's)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    import elemental_tpu as el
    from elemental_tpu.core.distmatrix import DistMatrix

    n = args.n
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    grid = el.Grid([topo.devices[0]])
    meta = DistMatrix(None, (n, n), el.MC, el.MR, 0, 0, grid)
    A = meta.with_local(jax.ShapeDtypeStruct(
        (n, n), jnp.float32, sharding=grid.sharding(meta.spec)))
    return _rehearse(jax.jit(lambda a: el.svd(a), donate_argnums=0), (A,),
                     args.hlo,
                     ("svd_route", "qdwh_step", "qdwh_stack_qr",
                      "polar_block", "herm_tridiag_hemv", "dc_merge"),
                     program="svd", n=n, grid=[grid.height, grid.width])


def _stripped(text):
    """Optimized HLO less what moves with a source line, a module's number
    or a scope's name: the metadata, the header's layout line, and every
    ``%name``, replaced by its rank of first appearance (XLA names a merged
    instruction after the tail of its merged ``op_name``: a scope opened
    inside a ``shard_map`` turns ``%transpose_transpose.84`` into
    ``%transpose.353`` and moves the numbers after it; PR 55)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    head, _, rest = text.partition("\n")
    starts = [i for i in (rest.find("\n%"), rest.find("\nENTRY")) if i >= 0]
    seen = {}
    return head.split(",")[0] + re.sub(
        r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
        rest[min(starts):])


def _drivers(_args):
    import jax
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_enable_compilation_cache", False)
    import numpy as np
    import elemental_tpu as el

    def line(grid_name, driver, fn, *operands):
        try:
            text = jax.jit(fn).lower(*operands).compile().as_text()
        except jax.errors.ConcretizationTypeError:
            # a tree whose driver reads a value on the host (svd before 53)
            print(grid_name, driver, "cannot-be-traced", 0, flush=True)
            return
        print(grid_name, driver,
              hashlib.sha256(_stripped(text).encode()).hexdigest()[:16],
              text.count("\n") + 1, flush=True)

    rng = np.random.default_rng(0)
    n = 256
    for grid_name, chips in (("1x1", 1), ("2x2", 4)):
        g = el.Grid(list(jax.devices()[:chips]))

        def dist(F):
            return el.from_global(F.astype(np.float32), el.MC, el.MR, grid=g)
        F = rng.normal(size=(n, n))
        S, G = dist(F @ F.T + n * np.eye(n)), dist(F)
        B = dist(rng.normal(size=(n, 8)))
        line(grid_name, "hpd_solve", lambda a, b: el.hpd_solve(a, b, nb=64),
             S, B)
        line(grid_name, "lu_solve", lambda a, b: el.lu_solve(a, b, nb=64),
             G, B)
        line(grid_name, "mixed_solve",
             lambda a, b: el.mixed_solve(a, b, nb=64), S, B)
        line(grid_name, "herm_eig",
             lambda a: el.herm_eig(a, nb=64, dc_min=32, repl_max=32), S)
        line(grid_name, "least_squares", el.least_squares,
             dist(rng.normal(size=(4096, 16))),
             dist(rng.normal(size=(4096, 4))))
        line(grid_name, "svd", lambda a: el.svd(a, nb=64), G)
        if chips > 1:
            # the tall route at a CPU size: the rule's aspect lowered while
            # the program is traced, as the tier-1 tests do
            import importlib
            qr = importlib.import_module("elemental_tpu.lapack.qr")
            aspect, qr._TALL_ASPECT = qr._TALL_ASPECT, 4
            try:
                # a new function: jax keys its trace cache by the function
                line(grid_name, "least_squares_tall",
                     lambda a, b: el.least_squares(a, b),
                     dist(rng.normal(size=(4096, 16))),
                     dist(rng.normal(size=(4096, 4))))
            finally:
                qr._TALL_ASPECT = aspect
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", help="import elemental_tpu from this tree")
    sub = parser.add_subparsers(dest="what", required=True)
    eig = sub.add_parser("eig")
    eig.add_argument("--n", type=int, default=16384)
    eig.add_argument("--stage", choices=STAGES, default="whole")
    eig.add_argument("--hlo", help="write the optimized HLO here")
    svd = sub.add_parser("svd")
    svd.add_argument("--n", type=int, default=16384)
    svd.add_argument("--hlo", help="write the optimized HLO here")
    sub.add_parser("drivers")
    args = parser.parse_args(argv)
    # a rehearsal describes a chip and a hash needs the virtual CPU mesh:
    # both want the CPU client, set before jax starts
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.root:
        sys.path.insert(0, os.path.abspath(args.root))
    return {"eig": _eig, "svd": _svd, "drivers": _drivers}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
