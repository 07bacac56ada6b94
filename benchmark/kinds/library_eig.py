"""Kind ``library_eig``: one compiled call of the public Hermitian
eigensolver, the full spectrum with vectors.

A configuration of this kind names the driver (``operator``:
``herm_eig``), the operand, ``n``, ``nb`` and the grid.  The session has
the contract of ``library_solve``'s: it builds the grid over the devices
it is given and compiles the cell's three programs -- generate A on the
device from a 32-bit key, the solve (A donated), and the check -- and the
harness drives them: ``prepare(i)`` (untimed) -> ``solve(operands)``
(timed by the harness, to ``block_until_ready`` on ``(w, Z)``) ->
``check(i, (w, Z))`` (untimed).

The solve passes the driver ``nb`` and nothing else: the approach, the
divide and conquer's thresholds and the precision are what the driver
picks, which is what users get and what is measured.  ``precision`` is
passed only by the lower-precision control (``tests/control_on_chip.py``),
never by a cell.

The check is ``reference_eig.residuals_eig`` on ``w`` and
``el.to_global(Z)`` and on A regenerated by the benchmark's own plain
generator, a block of rows at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import elemental_tpu as el

import reference
import reference_eig
import xplane

#: operator name -> public driver
OPERATORS = {"herm_eig": el.herm_eig}

#: the name the timed program carries into the device trace
SOLVE_NAME = "bench_solve"


def _key(seed, iteration):
    return np.asarray(reference.operand_key(seed, iteration, 0), np.uint32)


def programs(config, traffic, devices, precision=None):
    """(generate, bench_solve, check, grid): the cell's three programs as
    plain functions, before ``jit``."""
    n, nb = config["n"], config["nb"]
    dtype = jnp.dtype(config["dtype"])
    if dtype != jnp.float32:
        raise ValueError(f"operands are generated in float32, the "
                         f"configuration states {dtype}")
    if (traffic["vectors"], traffic["subset"]) != ("all", "none"):
        raise ValueError(f"this kind solves for the full spectrum with "
                         f"vectors; the mix asks for vectors "
                         f"{traffic['vectors']!r}, subset "
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
        return driver(A, nb=nb, **kwargs)

    # the check's own mesh over the same devices, for its A
    sharding = None
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices).reshape(config["grid"]),
                    ("rows", "cols"))
        sharding = NamedSharding(mesh, PartitionSpec("rows", "cols"))

    def check(key_a, w, Z):
        return reference_eig.residuals_eig(entry_a(n, key_a), n, w,
                                           el.to_global(Z), sharding)

    return generate, bench_solve, check, grid


class Session:
    """The compiled programs of one cell and what is known of them."""

    def __init__(self, config, traffic, devices, seed, precision=None):
        self.seed = seed
        n, nb = config["n"], config["nb"]
        generate, bench_solve, check, grid = programs(
            config, traffic, devices, precision)
        k = _key(seed, 0)
        self._generate = jax.jit(generate).lower(k).compile()
        (A,) = self.prepare(-1)                 # the warm-up's operand
        self._solve = jax.jit(bench_solve, donate_argnums=0).lower(
            A).compile()
        w, Z = self._solve(A)
        del A
        self._check = jax.jit(check).lower(k, w, Z).compile()
        self.warm = self.check(-1, (w, Z))

        mem = self._solve.memory_analysis()
        parts = {"argument": int(mem.argument_size_in_bytes),
                 "output": int(mem.output_size_in_bytes),
                 "temp": int(mem.temp_size_in_bytes),
                 "alias": int(mem.alias_size_in_bytes)}
        text = self._solve.as_text()
        self.facts = {
            "operator": config["operator"], "n": n, "nb": nb,
            "grid": [grid.height, grid.width], "chips": len(devices),
            "solve_module": f"jit_{SOLVE_NAME}",
            "flops_per_solve": reference_eig.eig_flops(n),
            "hemv_bytes": reference_eig.hemv_bytes(n),
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
        ``(w, Z)``."""
        return self._solve(*operands)

    def check(self, i, answer):
        """``{number compared: value}`` of solve ``i``'s answer."""
        out = self._check(_key(self.seed, i), *answer)
        return {name: float(v) for name, v in out.items()}


def setup(config, traffic, devices, seed):
    return Session(config, traffic, devices, seed)
