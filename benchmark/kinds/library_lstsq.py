"""Kind ``library_lstsq``: one compiled call of the public least-squares
driver on a tall-skinny operand.

A configuration of this kind names the driver (``operator``:
``least_squares``), the operand, ``rows``, ``cols`` and the grid; the
traffic mix gives ``nrhs``.  The session has the contract of
``library_solve``'s: it builds the grid over the devices it is given and
compiles the cell's three programs -- generate (A, B) on the device from
two 32-bit keys, the solve (A donated), and the check -- and the harness
drives them: ``prepare(i)`` (untimed) -> ``solve(operands)`` (timed by
the harness, to ``block_until_ready`` on X) -> ``check(i, X)`` (untimed).

The solve passes the driver ``nb`` where the configuration states one,
and nothing else: the route (which chips factor which rows) is what the
driver picks from the shapes and the grid, which is what users get and
what is measured.  ``precision`` is passed only by the lower-precision
control (``tests/control_on_chip.py``), never by a cell.

A program whose driver does not take the tall-skinny route here (it ticks
no ``lstsq_route{kind=tall}`` while the solve is traced) is refused before
anything of the solve is compiled: the blocked route gathers the whole
operand onto every chip, which no chip holds at a cell's size.

The check is ``reference_lstsq.residuals_lstsq`` on ``el.to_global(X)``
and on A and B regenerated by the benchmark's own plain generator, a
block of rows at a time (on a grid of several devices the block's rows
are spread over a mesh of the benchmark's own).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import elemental_tpu as el

import reference
import reference_lstsq
import xplane

#: operator name -> public driver
OPERATORS = {"least_squares": el.least_squares}

#: operand name -> f(n, key) -> f(i, j)
ENTRIES = {**reference.ENTRIES,
           "graded": reference_lstsq.entry_graded,
           "graded_collinear": reference_lstsq.entry_graded_collinear}

#: the name the timed program carries into the device trace
SOLVE_NAME = "bench_solve"


def _key(seed, iteration, which):
    return np.asarray(reference.operand_key(seed, iteration, which),
                      np.uint32)


def programs(config, traffic, devices, precision=None):
    """(generate, bench_solve, check, grid): the cell's three programs as
    plain functions, before ``jit``."""
    m, n, nrhs = config["rows"], config["cols"], traffic["nrhs"]
    dtype = jnp.dtype(config["dtype"])
    if dtype != jnp.float32:
        raise ValueError(f"operands are generated in float32, the "
                         f"configuration states {dtype}")
    driver = OPERATORS[config["operator"]]
    entry_a = ENTRIES[config["operand"]]
    entry_b = reference.entry_uniform_pm1
    grid = el.Grid(list(devices))
    if [grid.height, grid.width] != list(config["grid"]):
        raise ValueError(f"the configuration's grid {config['grid']} is "
                         f"not the {grid!r} that el.Grid() makes of "
                         f"{len(devices)} devices")

    def generate(key_a, key_b):
        A = el.index_dependent_fill(
            el.matrices.zeros(m, n, grid, dtype), entry_a(n, key_a))
        B = el.index_dependent_fill(
            el.matrices.zeros(m, nrhs, grid, dtype), entry_b(nrhs, key_b))
        return A, B

    kwargs = {"nb": config["nb"]} if "nb" in config else {}
    if precision is not None:
        kwargs["precision"] = precision

    def bench_solve(A, B):
        return driver(A, B, **kwargs)

    # the check's own mesh over the same devices: a block's rows over all
    sharding = None
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices), ("rows",))
        sharding = NamedSharding(mesh, PartitionSpec("rows", None))

    def check(key_a, key_b, X):
        return reference_lstsq.residuals_lstsq(
            entry_a(n, key_a), entry_b(nrhs, key_b), m, n, nrhs,
            el.to_global(X), sharding)

    return generate, bench_solve, check, grid


def _lower_tall(bench_solve, A, B):
    """The solve lowered, or SystemExit where the driver did not take the
    tall-skinny route while it was traced."""
    with el.obs.metrics_scope() as counters:
        lowered = jax.jit(bench_solve, donate_argnums=0).lower(A, B)
    if not counters.counter_value("lstsq_route", kind="tall"):
        raise SystemExit(
            "benchmark: this program's least_squares takes no tall-skinny "
            "route for this operand (no lstsq_route{kind=tall} was counted "
            "while the solve was traced); its blocked route gathers the "
            "whole operand onto every chip, and is not compiled here")
    return lowered


class Session:
    """The compiled programs of one cell and what is known of them."""

    def __init__(self, config, traffic, devices, seed, precision=None):
        self.seed = seed
        m, n, nrhs = config["rows"], config["cols"], traffic["nrhs"]
        generate, bench_solve, check, grid = programs(
            config, traffic, devices, precision)
        k = _key(seed, 0, 0)
        self._generate = jax.jit(generate).lower(k, k).compile()
        A, B = self.prepare(-1)                 # the warm-up's operands
        self._solve = _lower_tall(bench_solve, A, B).compile()
        X = self._solve(A, B)
        del A
        self._check = jax.jit(check).lower(k, k, X).compile()
        self.warm = self.check(-1, X)

        mem = self._solve.memory_analysis()
        parts = {"argument": int(mem.argument_size_in_bytes),
                 "output": int(mem.output_size_in_bytes),
                 "temp": int(mem.temp_size_in_bytes),
                 "alias": int(mem.alias_size_in_bytes)}
        text = self._solve.as_text()
        self.facts = {
            "operator": config["operator"], "rows": m, "cols": n,
            "nrhs": nrhs, "grid": [grid.height, grid.width],
            "chips": len(devices), "solve_module": f"jit_{SOLVE_NAME}",
            "flops_per_solve": reference_lstsq.lstsq_flops(m, n, nrhs),
            "lstsq_bytes": reference_lstsq.lstsq_bytes(m, n, nrhs),
            "plan_bytes": (parts["argument"] + parts["output"]
                           + parts["temp"] - parts["alias"]),
            "plan_parts": parts,
            "hlo_lines": text.count("\n"),
            "collectives": {c: text.count(f" {c}(") + text.count(
                f" {c}-start(") for c in xplane.COLLECTIVE_PREFIXES},
        }

    def prepare(self, i):
        """Operands of solve ``i``, made on the device; untimed."""
        return jax.block_until_ready(self._generate(
            _key(self.seed, i, 0), _key(self.seed, i, 1)))

    def solve(self, operands):
        """The timed call: the compiled public driver, A donated."""
        return self._solve(*operands)

    def check(self, i, X):
        """``{number compared: value}`` of solve ``i``'s answer."""
        out = self._check(_key(self.seed, i, 0), _key(self.seed, i, 1), X)
        return {name: float(v) for name, v in out.items()}


def setup(config, traffic, devices, seed):
    return Session(config, traffic, devices, seed)
