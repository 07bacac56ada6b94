"""The three parts of an exchange in the compiled program (ISSUE 55).

Under every ``el.redist.<name>`` scope the engine's primitives name what
they emit ``pack`` (the local ops that feed an explicit collective),
``wire`` (the collective) or ``unpack`` (the local ops after it; an
exchange with no collective is all ``unpack``); grammar in
``elemental_tpu/obs/__init__.py``.  On the 2x2 CPU mesh, over the engine's
routes: every collective instruction holds ``wire`` as its part; nothing
else does but what the compiler made OF a collective (the CPU backend
splits an ``all_to_all`` into slices and a tuple form: they keep its
``op_name``, whose last segment is the collective's primitive); every other
instruction under an ``el.redist.`` name holds ``pack`` or ``unpack`` (a
literal the compiler materialises, a ``constant`` or its ``broadcast``,
carries the enclosing scope's name and no part); the motion the compiler
plans (``el.redist.row_permute``) holds none.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs
from elemental_tpu.core.compat import shard_map
from elemental_tpu.core.distmatrix import DistMatrix
from elemental_tpu.lapack import condense
from elemental_tpu.redist import engine

M, N = 52, 36

COLLECTIVES = ("all-gather", "all-to-all", "all-reduce",
               "collective-permute", "reduce-scatter")
#: the last ``op_name`` segment of what the compiler makes of a collective
PRIMITIVES = ("all_gather", "all_to_all", "ppermute", "psum",
              "psum_scatter", "reduce_scatter")
#: a literal's instructions: named by the enclosing scope, timed nowhere
LITERALS = ("constant", "broadcast")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                    r"([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _grid():
    return el.Grid(list(jax.devices()[:4]))


def _dist(cdist, rdist, shape=(M, N), calign=0, ralign=0, dtype=np.float32):
    F = np.random.default_rng(0).normal(size=shape).astype(dtype)
    return el.from_global(F, cdist, rdist, calign=calign, ralign=ralign,
                          grid=_grid())


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_TO_APPLY = re.compile(r"to_apply=%?([\w.\-]+)")


def instructions(text):
    """``[(name, opcode, el.redist.* name or None, part or None, the path's
    last segment)]`` of every instruction of an optimized HLO text that
    carries an ``op_name``, the reducers a collective applies left out
    (their ``add`` is the collective's own)."""
    out, reducers, current = [], set(), None
    for line in text.split("\n"):
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            current = c.group(1) if c else current
            continue
        op = _OP_NAME.search(line)
        if op is None:
            continue
        if m.group(3).startswith(COLLECTIVES) and _TO_APPLY.search(line):
            reducers.add(_TO_APPLY.search(line).group(1))
        segs = op.group(1).split("/")
        at = next((i for i, s in enumerate(segs)
                   if s.startswith("el.redist.")), None)
        hop = part = None
        if at is not None:
            hop = segs[at]
            part = next((s for s in segs[at + 1:]
                         if s in obs.REDIST_PARTS), None)
        out.append((current, m.group(1), m.group(3), hop, part, segs[-1]))
    return [row[1:] for row in out if row[0] not in reducers]


def check_parts(text, parts, collectives):
    """The rules of the module docstring on one program; ``parts`` the set
    the program must hold under its ``el.redist.`` names, ``collectives``
    whether it must hold a collective there."""
    found, wired = set(), 0
    for name, opcode, hop, part, last in instructions(text):
        collective = opcode.startswith(COLLECTIVES)
        if hop is None:
            assert not collective, f"{name}: a collective under no name"
            continue
        if hop == "el.redist.row_permute":
            assert part is None, (name, opcode, part)
            continue
        if collective:
            assert part == "wire", (name, opcode, hop, part)
            wired += 1
        elif part == "wire":
            assert last.split(";")[0] in PRIMITIVES, (name, opcode, last)
        elif part is None:
            assert opcode in LITERALS, (name, opcode, hop)
        if part is not None:
            found.add(part)
    assert found == set(parts), (found, parts)
    assert (wired > 0) == collectives


def _redistribute(src, dst, **kw):
    align = {k: kw.pop(k) for k in ("src_calign", "src_ralign") if k in kw}

    def build():
        A = _dist(*src, calign=align.get("src_calign", 0),
                  ralign=align.get("src_ralign", 0))
        return (lambda a: el.redistribute(a, *dst, **kw)), (A,)
    return build


def _panel_spread(comm_precision):
    def build():
        A = _dist(el.VC, el.STAR, shape=(M, 8))
        return (lambda a: el.panel_spread(
            a, comm_precision=comm_precision)), (A,)
    return build


def _contract(src, dst):
    """``engine.contract`` inside a ``shard_map``, under a name of the
    caller's, as a driver would open it."""
    def build():
        A = _dist(*src)
        out = DistMatrix(None, A.gshape, *dst, 0, 0, A.grid)

        def f(a):
            with jax.named_scope("el.redist.contract"):
                return shard_map(lambda x: engine.contract(x, *dst),
                                 mesh=A.grid.mesh, in_specs=(A.spec,),
                                 out_specs=out.spec, check_vma=False)(a)
        return f, (A,)
    return build


def _row_moves(full):
    def build():
        A = _dist(el.MC, el.MR)
        if full:
            perm = jnp.arange(M)[::-1]
            return (lambda a: engine.permute_rows_storage(a, perm)), (A,)
        targets, sources = jnp.arange(4), jnp.arange(4) + 7
        return (lambda a: engine.move_rows(
            a, targets, sources, jnp.ones(4, bool))), (A,)
    return build


MC, MR, VC, VR, STAR, MD = el.MC, el.MR, el.VC, el.VR, el.STAR, el.MD
ALL = ("pack", "wire", "unpack")

#: id -> (program, the parts it must hold, whether it holds a collective)
CASES = {
    # the fused [MC,MR] <-> [VC,STAR] pair and its transposed forms
    "to_v": (_redistribute((MC, MR), (VC, STAR)), ("wire", "unpack"), True),
    "to_v.padded": (lambda: (lambda a: el.redistribute(a, VC, STAR),
                             (_dist(MC, MR, shape=(50, N)),)), ALL, True),
    "from_v": (_redistribute((VC, STAR), (MC, MR)), ("wire", "unpack"),
               True),
    "to_star_v": (_redistribute((MC, MR), (STAR, VR)), ALL, True),
    "from_star_v": (_redistribute((STAR, VR), (MC, MR)), ALL, True),
    # gather chains to [STAR,STAR]
    "gather.fused": (_redistribute((MC, MR), (STAR, STAR)),
                     ("wire", "unpack"), True),
    "gather.v": (_redistribute((VC, STAR), (STAR, STAR)),
                 ("wire", "unpack"), True),
    "gather.mr_mc": (_redistribute((MR, MC), (STAR, STAR)),
                     ("wire", "unpack"), True),
    "gather.aligned": (_redistribute((MC, STAR), (STAR, STAR),
                                     src_calign=1), ("wire", "unpack"),
                       True),
    "gather.md": (_redistribute((MD, STAR), (STAR, STAR)),
                  ("wire", "unpack"), True),
    # the partial ladder V* <-> M*
    "ladder.up": (_redistribute((VC, STAR), (MC, STAR)), ("wire", "unpack"),
                  True),
    "ladder.down": (_redistribute((MC, STAR), (VC, STAR)), ("unpack",),
                    False),
    "ladder.permute": (_redistribute((VC, STAR), (VR, STAR)), ("wire",),
                       True),
    "ladder.columns": (_redistribute((STAR, VR), (STAR, MR)),
                       ("wire", "unpack"), True),
    # filter-only entries: no collective, all unpack
    "filter": (_redistribute((STAR, STAR), (MC, MR)), ("unpack",), False),
    "filter.v": (_redistribute((STAR, STAR), (VC, STAR)), ("unpack",),
                 False),
    "filter.md": (_redistribute((STAR, STAR), (MD, STAR)), ("unpack",),
                  False),
    # chains of hops, a re-alignment
    "chain.transpose": (_redistribute((MC, MR), (MR, MC)),
                        ("wire", "unpack"), True),
    "chain.mr_star": (_redistribute((MC, MR), (MR, STAR)),
                      ("wire", "unpack"), True),
    "realign": (_redistribute((MC, MR), (MC, MR), calign=1), ("wire",),
                True),
    # narrow wires
    "to_v.bf16": (_redistribute((MC, MR), (VC, STAR),
                                comm_precision="bf16"), ALL, True),
    "gather.int8": (_redistribute((MC, MR), (STAR, STAR),
                                  comm_precision="int8"), ALL, True),
    # the fused panel spread
    "panel_spread": (_panel_spread(None), ("wire", "unpack"), True),
    "panel_spread.bf16": (_panel_spread("bf16"), ALL, True),
    "panel_spread.int8": (_panel_spread("int8"), ALL, True),
    # the one-shot plan
    "direct": (_redistribute((MC, MR), (VC, STAR), path="direct"), ALL,
               True),
    "direct.int8": (_redistribute((MC, MR), (STAR, VR), path="direct",
                                  comm_precision="int8"), ALL, True),
    "direct.local": (_redistribute((STAR, STAR), (MC, MR), path="direct"),
                     ("unpack",), False),
    # contract: reduce-scatter and all-reduce
    "contract.scatter": (_contract((MC, STAR), (MC, MR)), ("pack", "wire"),
                         True),
    "contract.both": (_contract((STAR, STAR), (MC, MR)), ("pack", "wire"),
                      True),
    "contract.sum": (_contract((STAR, STAR), (STAR, STAR)), ("wire",),
                     True),
    # motion the compiler plans: no part
    "move_rows": (_row_moves(False), (), False),
    "permute_rows": (_row_moves(True), (), False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_exchange_names_its_parts(case):
    build, parts, collectives = CASES[case]
    fn, operands = build()
    text = jax.jit(fn).lower(*operands).compile().as_text()
    names = {hop for _n, _o, hop, _p, _l in instructions(text)}
    assert any(names - {None}), "no el.redist. name in the program"
    check_parts(text, parts, collectives)


def test_hemv_join_is_all_wire(monkeypatch):
    """The grid tridiagonalization on its TPU path (the kernel interpreted):
    the column's one all-reduce reads ``el.redist.hemv_join``."""
    monkeypatch.setattr(condense, "_reads_triangle_once", lambda A: True)
    n = 44
    F = np.random.default_rng(3).normal(size=(n, n)).astype(np.float32)
    A = el.from_global(F + F.T, el.MC, el.MR, grid=_grid())
    text = jax.jit(lambda a: condense.hermitian_tridiag(a, nb=8)).lower(
        A).compile().as_text()
    joins = [(opcode, part) for _n, opcode, hop, part, _l
             in instructions(text) if hop == "el.redist.hemv_join"]
    assert joins and all(part == "wire" for _o, part in joins)
    assert any(opcode.startswith("all-reduce") for opcode, _p in joins)
    check_parts(text, ("wire", "unpack"), True)


def test_the_helper_takes_the_three_names_only():
    assert obs.REDIST_PARTS == ("pack", "wire", "unpack")
    for part in obs.REDIST_PARTS:
        with obs.redist_part(part):
            pass
    with pytest.raises(ValueError, match="part must be one of"):
        obs.redist_part("planned")
