"""Pallas kernels: the fused panel kernels of the factorization critical
path (ISSUE 17), the one-pass triangle ``symv`` of the
tridiagonalization's column loop (ISSUE 44) and the unpivoted block LU of
the mixed-precision factor's diagonal blocks (ISSUE 46).

Panel factorization is the serial spine of every blocked schedule: the
LU chunk ladder, the Cholesky diagonal-block factor/inverse pair, and
the QR larfg chain each lower to dozens of small XLA ops whose launch
and layout overhead dominates at small nb.  This package fuses each
primitive into one ``pallas_call`` that keeps the replicated panel
resident in VMEM:

* :func:`lu_panel` -- pivot search + column scale + rank-1/chunked
  trailing updates, twin of ``lapack.lu._panel_lu`` (identical pivot
  sequence, factor to rounding);
* :func:`potrf_inv` -- blocked potrf + triangular inverse, twin of
  ``lapack.cholesky._potrf_inv_impl`` (residual-bounded);
* :func:`qr_panel` -- larfg reflector chain + larft T build, twin of
  ``lapack.qr._panel_qr`` + ``_larft`` (residual-bounded).

Beside them, :func:`symv_lower` -- ``(tril(A) + stril(A)^T) x`` from one
read of the stored lower triangle, a grid over its tiles alone -- is what
``lapack.condense.hermitian_tridiag`` multiplies by once a column on one
TPU chip and, each chip on its own shard through the kernel's shard form
(``symv.symv_lower_shard``, inside a ``shard_map``), on a square grid of
them (no knob: the driver decides from its input,
``condense._reads_triangle_once``), and the first kernel of this package
a benchmark cell runs (``heig.1x1.b2b``, since PR 52 ``heig.2x2.b2b``).  And :func:`lu_nopiv_block` --
the unpivoted column recurrence of one square block, resident in VMEM:
``lu_panel``'s unblocked mode without its pivot search, row swap and
pivot output, twin of the ``fori_loop`` inside ``lapack.lu._lu_nopiv`` --
is what ``lapack.mixed.lu_nopiv`` factors each sub-block of a diagonal
block with on one TPU chip (no knob either:
``mixed._diag_blocks_in_vmem``; cell ``hplmxp.1x1.b2b``).

The panel kernels are selected by the ``panel_impl='xla'|'pallas'|'auto'``
knob on ``lu`` / ``cholesky`` / ``qr``: :func:`resolve_panel` turns the
resolved knob into a :class:`PanelPlan`, and each call site asks
``plan.use_pallas(shape, dtype)`` -- a STATIC trace-time gate that
falls back to the XLA twin for complex dtypes and for panels whose
working set exceeds the VMEM budget, so the fused kernels never
silently spill.  On the CPU backend the kernels run under
``pl.pallas_call(interpret=True)``, which is how CPU CI pins the twins
(see ``tests/kernels/``); on a TPU backend they are compiled by Mosaic
(``tests/test_chip_compile.py`` compiles each for a described v5e).

Panels are replicated-local compute: a ``pallas_call`` is a local
primitive with no collectives, so every comm-plan golden is byte-
identical under either implementation (gated by ``tools/check.sh
kernels``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .common import (LANE, PANEL_VMEM_BUDGET, PANEL_VMEM_LIMIT, SUBLANE,
                     interpret_default, pad_tiles, panel_fits, round_up)
from .lu_panel import lu_panel
from .chol_panel import potrf_inv
from .qr_panel import qr_panel
from .symv import symv_lower
from .lu_nopiv_block import lu_nopiv_block

#: implementations the ``panel_impl`` knob enumerates ('auto' resolves
#: to one of these); 'xla' first, so ties in the tuner's cost ranking
#: keep the status-quo path (same convention as tune.knobs.LU_PANELS).
PANEL_IMPLS = ("xla", "pallas")

#: LU chunk ladder (512/64; no other ladder has a ledger line).  Single
#: source of truth -- lapack.lu reads it through default_inners() /
#: resolve_panel() rather than importing a bare module constant that
#: monkeypatching would silently go stale on (the ISSUE 17 staleness
#: footgun).
DEFAULT_INNERS = (512, 64)


def default_inners() -> tuple:
    """The pinned LU panel chunk ladder (see :data:`DEFAULT_INNERS`)."""
    return DEFAULT_INNERS


@dataclass(frozen=True)
class PanelPlan:
    """Resolved panel-implementation choice plus its provenance.

    ``impl`` is the post-'auto' knob value; ``inners`` is the LU chunk
    ladder the XLA path recurses on AND the width the fused kernel's
    blocked mode uses (``pallas_inner``); ``source`` records where the
    choice came from ('default', 'explicit', 'tuned', 'complex-xla').
    """

    impl: str = "xla"
    inners: tuple = DEFAULT_INNERS
    source: str = "default"

    def use_pallas(self, shape, dtype, copies: int = 3) -> bool:
        """Static per-call-site gate: fused kernel only for real dtypes
        whose padded working set (``copies`` VMEM residents) fits the
        budget; everything else stays on the XLA twin."""
        if self.impl != "pallas":
            return False
        if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
            return False
        return panel_fits(shape, dtype, copies=copies)

    @property
    def pallas_inner(self) -> int:
        """Chunk width for the fused LU kernel's blocked mode: the
        finest rung of the ladder (coarser rungs exist to amortize XLA
        launches, which the fused kernel has already paid once)."""
        return int(self.inners[-1]) if self.inners else 0

    def to_doc(self) -> dict:
        return {"impl": self.impl, "inners": list(self.inners),
                "source": self.source}


def resolve_panel(panel_impl=None, *, dtype=None, inners=None,
                  source: str | None = None) -> PanelPlan:
    """Turn a resolved ``panel_impl`` knob value into a
    :class:`PanelPlan`.

    ``None`` means the status-quo XLA path.  'auto' is resolved by
    ``tune.resolve_knobs`` BEFORE this point -- drivers never pass it
    here, and one that does is refused.  Complex dtypes fall back to
    'xla' silently by design: the knob is a performance hint and the XLA twin is the same math, so a
    complex matrix through ``panel_impl='pallas'`` must factor, not
    raise (pinned by tests/kernels/test_dispatch.py).
    """
    impl = "xla" if panel_impl is None else str(panel_impl)
    if impl not in PANEL_IMPLS:
        raise ValueError(
            f"panel_impl must be one of {PANEL_IMPLS} by the time it is "
            f"resolved ('auto' is the tuner's to turn into one), "
            f"got {panel_impl!r}")
    src = source if source is not None else (
        "default" if panel_impl is None else "explicit")
    if (impl == "pallas" and dtype is not None
            and jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating)):
        impl, src = "xla", "complex-xla"
    lad = default_inners() if inners is None else tuple(
        int(i) for i in inners)
    return PanelPlan(impl=impl, inners=lad, source=src)
