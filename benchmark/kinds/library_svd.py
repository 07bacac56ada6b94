"""Kind ``library_svd``: one compiled call of the public singular value
decomposition, all n triplets of a square matrix.

A configuration of this kind names the driver (``operator``: ``svd``),
the operand, ``n`` and the grid, and NO block size.  The session has the
contract of ``library_eig``'s: it builds the grid over the devices it is
given and compiles the cell's three programs -- generate A on the device
from a 32-bit key, the solve (A donated), and the check -- and the
harness drives them: ``prepare(i)`` (untimed) -> ``solve(operands)``
(timed by the harness, to ``block_until_ready`` on ``(U, s, V)``) ->
``check(i, (U, s, V))`` (untimed).

The solve passes the driver nothing but A: the approach, every stage's
block and the precision are what the driver picks, which is what users
get and what is measured.  ``precision`` is passed only by the
lower-precision control (``tests/control_on_chip.py``), never by a cell.

A program whose ``svd`` cannot be traced as one program (it reads a value
on the host), or whose traced solve ticks no ``svd_route{approach=polar}``
(another route behind the same name), is refused before anything of the
solve is compiled.

The check is ``reference_svd.residuals_svd`` on ``s`` and on
``el.to_global`` of U and V, and on A regenerated by the benchmark's own
plain generator, a block of rows at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import elemental_tpu as el

import reference
import reference_svd
import xplane

#: operator name -> public driver
OPERATORS = {"svd": el.svd}

#: the name the timed program carries into the device trace
SOLVE_NAME = "bench_solve"

#: the trace-time counters of the solve that the facts carry
COUNTERS = ("svd_route", "qdwh_step", "polar_block")


def _key(seed, iteration):
    return np.asarray(reference.operand_key(seed, iteration, 0), np.uint32)


def programs(config, traffic, devices, precision=None):
    """(generate, bench_solve, check, grid): the cell's three programs as
    plain functions, before ``jit``."""
    n = config["n"]
    dtype = jnp.dtype(config["dtype"])
    if dtype != jnp.float32:
        raise ValueError(f"operands are generated in float32, the "
                         f"configuration states {dtype}")
    if (traffic["vectors"], traffic["subset"]) != ("all", "none"):
        raise ValueError(f"this kind solves for every triplet; the mix "
                         f"asks for vectors {traffic['vectors']!r}, subset "
                         f"{traffic['subset']!r}")
    driver = OPERATORS[config["operator"]]
    entry_a = reference.ENTRIES[config["operand"]]
    grid = el.Grid(list(devices))
    if [grid.height, grid.width] != list(config["grid"]):
        raise ValueError(f"the configuration's grid {config['grid']} is "
                         f"not the {grid!r} that el.Grid() makes of "
                         f"{len(devices)} devices")

    def generate(key_a):
        return el.index_dependent_fill(
            el.matrices.zeros(n, n, grid, dtype), entry_a(n, key_a))

    kwargs = {} if precision is None else {"precision": precision}

    def bench_solve(A):
        return driver(A, **kwargs)

    # the check's own mesh over the same devices, for its A
    sharding = None
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices).reshape(config["grid"]),
                    ("rows", "cols"))
        sharding = NamedSharding(mesh, PartitionSpec("rows", "cols"))

    def check(key_a, U, s, V):
        return reference_svd.residuals_svd(
            entry_a(n, key_a), n, el.to_global(U), s, el.to_global(V),
            sharding)

    return generate, bench_solve, check, grid


def _labels(counters, name):
    """``{"k=v,...": count}`` of one counter family."""
    return {",".join(f"{k}={v}" for k, v in labels): int(count)
            for (_name, labels), count in counters.counters(name).items()}


def _lower_polar(bench_solve, A):
    """The solve lowered and its trace-time counters, or SystemExit where
    the driver cannot be traced or took another route than the polar one."""
    try:
        with el.obs.metrics_scope() as counters:
            lowered = jax.jit(bench_solve, donate_argnums=0).lower(A)
    except jax.errors.ConcretizationTypeError as e:
        raise SystemExit(
            "benchmark: this program's svd reads a value on the host while "
            "it is traced, so it is not one compiled program and is not "
            f"run here: {str(e).splitlines()[0]}")
    if not counters.counter_value("svd_route", approach="polar"):
        raise SystemExit(
            "benchmark: this program's svd counted no "
            "svd_route{approach=polar} while the solve was traced: not the "
            "QDWH polar route, and it is not compiled here")
    return lowered, {name: _labels(counters, name) for name in COUNTERS}


class Session:
    """The compiled programs of one cell and what is known of them."""

    def __init__(self, config, traffic, devices, seed, precision=None):
        self.seed = seed
        n = config["n"]
        generate, bench_solve, check, grid = programs(
            config, traffic, devices, precision)
        k = _key(seed, 0)
        self._generate = jax.jit(generate).lower(k).compile()
        (A,) = self.prepare(-1)                 # the warm-up's operand
        lowered, counted = _lower_polar(bench_solve, A)
        self._solve = lowered.compile()
        U, s, V = self._solve(A)
        del A
        self._check = jax.jit(check).lower(k, U, s, V).compile()
        self.warm = self.check(-1, (U, s, V))

        mem = self._solve.memory_analysis()
        parts = {"argument": int(mem.argument_size_in_bytes),
                 "output": int(mem.output_size_in_bytes),
                 "temp": int(mem.temp_size_in_bytes),
                 "alias": int(mem.alias_size_in_bytes)}
        text = self._solve.as_text()
        kinds = reference_svd.qdwh_step_kinds()
        self.facts = {
            "operator": config["operator"], "n": n,
            "grid": [grid.height, grid.width], "chips": len(devices),
            "solve_module": f"jit_{SOLVE_NAME}",
            "flops_per_solve": reference_svd.svd_flops(n, kinds),
            "polar_flops": reference_svd.polar_flops(n, kinds),
            **counted,
            "plan_bytes": (parts["argument"] + parts["output"]
                           + parts["temp"] - parts["alias"]),
            "plan_parts": parts,
            "hlo_lines": text.count("\n"),
            "collectives": {c: text.count(f" {c}(") + text.count(
                f" {c}-start(") for c in xplane.COLLECTIVE_PREFIXES},
        }

    def prepare(self, i):
        """The operand of solve ``i``, made on the device; untimed."""
        return (jax.block_until_ready(self._generate(_key(self.seed, i))),)

    def solve(self, operands):
        """The timed call: the compiled public driver, A donated; returns
        ``(U, s, V)``."""
        return self._solve(*operands)

    def check(self, i, answer):
        """``{number compared or printed: value}`` of solve ``i``'s
        answer."""
        out = self._check(_key(self.seed, i), *answer)
        return {name: float(v) for name, v in out.items()}


def setup(config, traffic, devices, seed):
    return Session(config, traffic, devices, seed)
