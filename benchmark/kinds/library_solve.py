"""Kind ``library_solve``: one compiled call of a public solve driver.

A configuration of this kind names a driver of ``elemental_tpu``
(``operator``), the operand, ``n``, ``nb`` and the grid.  The session
builds the grid over the devices it is given and compiles the cell's
three programs -- generate (A, B) on the device from a 32-bit key, the
solve (A donated), and the check -- and the harness drives them:
``prepare(i)`` (untimed) -> ``solve(operands)`` (timed by the harness)
-> ``check(i, X)`` (untimed).

The solve passes the driver ``nb`` and nothing else: whatever ``'auto'``
picks inside is what users get and what is measured.  ``precision`` is
passed only by the lower-precision control (``tests/control_on_chip.py``),
never by a cell.

The check is ``reference.residuals`` on ``el.to_global(X)`` and on A and
B regenerated by the benchmark's own plain generator, a block of rows at
a time (an ordinary array; on a grid of several devices a
``NamedSharding`` over a mesh of the benchmark's own).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import elemental_tpu as el

import reference
import xplane

#: operator name -> (public driver, the factorization its flops follow)
OPERATORS = {"hpd_solve": (el.hpd_solve, "cholesky"),
             "lu_solve": (el.lu_solve, "lu")}

#: the name the timed program carries into the device trace
SOLVE_NAME = "bench_solve"


def _key(seed, iteration, which):
    return np.asarray(reference.operand_key(seed, iteration, which),
                      np.uint32)


def programs(config, traffic, devices, precision=None):
    """(generate, bench_solve, check, grid, factor): the cell's three
    programs as plain functions, before ``jit``."""
    n, nrhs, nb = config["n"], traffic["nrhs"], config["nb"]
    dtype = jnp.dtype(config["dtype"])
    if dtype != jnp.float32:
        raise ValueError(f"operands are generated in float32, the "
                         f"configuration states {dtype}")
    driver, factor = OPERATORS[config["operator"]]
    entry_a = reference.ENTRIES[config["operand"]]
    entry_b = reference.ENTRIES["uniform_pm1"]
    grid = el.Grid(list(devices))
    if [grid.height, grid.width] != list(config["grid"]):
        raise ValueError(f"the configuration's grid {config['grid']} is "
                         f"not the {grid!r} that el.Grid() makes of "
                         f"{len(devices)} devices")

    def generate(key_a, key_b):
        A = el.index_dependent_fill(
            el.matrices.zeros(n, n, grid, dtype), entry_a(n, key_a))
        B = el.index_dependent_fill(
            el.matrices.zeros(n, nrhs, grid, dtype), entry_b(n, key_b))
        return A, B

    kwargs = {} if precision is None else {"precision": precision}

    def bench_solve(A, B):
        return driver(A, B, nb=nb, **kwargs)

    # the check's own mesh over the same devices, for its A
    sharding = None
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices).reshape(config["grid"]),
                    ("rows", "cols"))
        sharding = NamedSharding(mesh, PartitionSpec("rows", "cols"))

    def check(key_a, key_b, X):
        return reference.residuals(entry_a(n, key_a), entry_b(n, key_b),
                                   n, nrhs, el.to_global(X), sharding)

    return generate, bench_solve, check, grid, factor


class Session:
    """The compiled programs of one cell and what is known of them."""

    def __init__(self, config, traffic, devices, seed, precision=None):
        self.seed = seed
        n, nrhs, nb = config["n"], traffic["nrhs"], config["nb"]
        generate, bench_solve, check, grid, factor = programs(
            config, traffic, devices, precision)
        k = _key(seed, 0, 0)
        self._generate = jax.jit(generate).lower(k, k).compile()
        A, B = self.prepare(-1)                 # the warm-up's operands
        self._solve = jax.jit(bench_solve, donate_argnums=0).lower(
            A, B).compile()
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
            "operator": config["operator"], "n": n, "nrhs": nrhs, "nb": nb,
            "grid": [grid.height, grid.width], "chips": len(devices),
            "solve_module": f"jit_{SOLVE_NAME}",
            "flops_per_solve": reference.solve_flops(factor, n, nrhs),
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
