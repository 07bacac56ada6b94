"""Kind ``library_mxp``: one compiled call of the public mixed-precision
solve (HPL-MxP's shape): an LU factorization without pivoting whose
trailing updates run in one bfloat16 pass, the first solve and the
refinement to the float32 limit, all inside the one timed program.

A configuration of this kind names the driver (``operator``:
``mixed_solve``), the operand, ``n``, ``nb`` and the grid; the traffic mix
gives ``nrhs``.  The session has ``library_solve``'s contract: it builds
the grid over the devices it is given and compiles the cell's three
programs -- generate (A, B) on the device from two 32-bit keys, the solve
(A donated: the program keeps what it needs of it), and the check -- and
the harness drives them: ``prepare(i)`` (untimed) -> ``solve(operands)``
(timed by the harness, to ``block_until_ready`` on X) -> ``check(i, X)``
(untimed).

The solve passes the driver ``nb`` and nothing else: how many refinement
steps run is decided by the program, on the device, and no tolerance
comes from here.  ``precision`` (the HIGH side: panels, sweeps, residual)
is passed only by a lower-precision control
(``tests/control_on_chip.py``'s ``Session(..., precision=)``) and
``max_steps`` only by an unrefined one (``tests/test_library_mxp.py``),
never by a cell.  The readings a limit is set from come from
``tests/mxp_rows_on_chip.py``: at one right-hand side ``precision`` does
not reach the residual (a matrix-vector product runs on the vector unit in
exact float32), so ``control_on_chip.py`` alone cannot fail this kind.

A program whose driver does not factor without pivoting with bfloat16
update operands here (it ticks no ``lu_nopiv_step`` and no
``mixed_update{dtype=bfloat16}`` while the solve is traced) is refused
before anything of the solve is compiled: a pivoted or a float32
factorization behind the same name is a different deployment.

The check is ``reference.residuals`` on ``el.to_global(X)`` and on A and
B regenerated by the benchmark's own plain generator, a block of rows at
a time; ``backward_error`` is compared, HPL's scaled residual (float32's
eps), the program's own count of refinement steps and its own estimate
of the backward error are printed beside it.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import elemental_tpu as el

import reference
import reference_mxp
import xplane

#: operator name -> public driver (None: a program from before it had one)
OPERATORS = {"mixed_solve": getattr(el, "mixed_solve", None)}

#: operand name -> f(n, key) -> f(i, j)
ENTRIES = {**reference.ENTRIES,
           "shifted_pm1": reference_mxp.entry_shifted_pm1}

#: the name the timed program carries into the device trace
SOLVE_NAME = "bench_solve"


def _key(seed, iteration, which):
    return np.asarray(reference.operand_key(seed, iteration, which),
                      np.uint32)


def programs(config, traffic, devices, precision=None, max_steps=None):
    """(generate, bench_solve, check, grid): the cell's three programs as
    plain functions, before ``jit``.  ``bench_solve`` returns the driver's
    ``(X, info)``."""
    n, nrhs, nb = config["n"], traffic["nrhs"], config["nb"]
    dtype = jnp.dtype(config["dtype"])
    if dtype != jnp.float32:
        raise ValueError(f"operands are generated in float32, the "
                         f"configuration states {dtype}")
    driver = OPERATORS[config["operator"]]
    if driver is None:
        raise SystemExit(f"benchmark: this program has no "
                         f"el.{config['operator']}: it cannot run a "
                         f"configuration of kind library_mxp")
    entry_a = ENTRIES[config["operand"]]
    entry_b = reference.entry_uniform_pm1
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
    if max_steps is not None:
        kwargs["max_steps"] = max_steps

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

    return generate, bench_solve, check, grid


def _lower_mixed(bench_solve, A, B):
    """The solve lowered, or SystemExit where the driver did not factor
    without pivoting with bfloat16 update operands while it was traced."""
    with el.obs.metrics_scope() as counters:
        lowered = jax.jit(bench_solve, donate_argnums=0).lower(A, B)
    steps = counters.counter_value("lu_nopiv_step")
    updates = counters.counter_value("mixed_update", dtype="bfloat16")
    if not steps or not updates:
        raise SystemExit(
            f"benchmark: this program's mixed_solve counted "
            f"{steps:g} lu_nopiv_step and {updates:g} "
            f"mixed_update{{dtype=bfloat16}} while the solve was traced: "
            f"no unpivoted factorization with bfloat16 update operands, "
            f"and it is not compiled here")
    return lowered, int(steps), int(updates)


class Session:
    """The compiled programs of one cell and what is known of them."""

    def __init__(self, config, traffic, devices, seed, precision=None,
                 max_steps=None):
        self.seed = seed
        n, nrhs, nb = config["n"], traffic["nrhs"], config["nb"]
        generate, bench_solve, check, grid = programs(
            config, traffic, devices, precision, max_steps)
        k = _key(seed, 0, 0)
        self._generate = jax.jit(generate).lower(k, k).compile()
        A, B = self.prepare(-1)                 # the warm-up's operands
        lowered, steps, updates = _lower_mixed(bench_solve, A, B)
        self._solve = lowered.compile()
        X = self.solve((A, B))
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
            "flops_per_solve": reference_mxp.mxp_flops(n),
            "mxp_update_flops": reference_mxp.update_flops(n, nb),
            "lu_nopiv_steps": steps, "mixed_updates": updates,
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
        """The timed call: the compiled public driver, A donated.  Hands
        the harness X and keeps the program's ``info`` for the check."""
        X, self._info = self._solve(*operands)
        return X

    def check(self, i, X):
        """``{number compared or printed: value}`` of solve ``i``'s answer:
        the check's own numbers, then what the program said of the solve
        that made X (the last one)."""
        out = self._check(_key(self.seed, i, 0), _key(self.seed, i, 1), X)
        numbers = {name: float(v) for name, v in out.items()}
        numbers["refine_steps"] = float(self._info["steps"])
        numbers["program_backward_error"] = float(
            self._info["backward_error"])
        return numbers


def setup(config, traffic, devices, seed):
    return Session(config, traffic, devices, seed)
