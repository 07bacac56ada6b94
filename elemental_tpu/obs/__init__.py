"""Unified runtime observability (ISSUE 5): spans, metrics, exporters.

Four pieces, one subsystem -- the layer every perf PR reports through:

  :mod:`.tracer`       span tracer: ``Tracer`` (explicit nested spans via
                       ``span()``, driver tick channels, engine collective
                       observer) + :func:`phase_hook`, the one-line driver
                       integration all six tuned drivers call, and the
                       hook's scoped form ``tm.phase(phase, step)``: the
                       ONE way a driver marks a phase, in both modes
  :mod:`.metrics`      counters / gauges / histograms ->
                       ``obs_metrics/v1`` (op invocation counts,
                       redistribute calls/bytes, tuning-cache events,
                       phase-time histograms)
  :mod:`.phase_timer`  ``PhaseTimer`` -- per-phase wall-clock of an
                       EAGER run, a shim over the tracer
                       (``phase_timings/v1`` unchanged)
  :mod:`.export`       Chrome-trace/Perfetto ``trace.json`` rendering
                       (thread-keyed tracks + request flow events)
  :mod:`.compile_log`  the other half of a program's life (ISSUE 39):
                       what JAX traced, lowered, compiled or read back
                       from the persistent cache, from ``jax.monitoring``
                       -> self seconds per stage, the cache's counters,
                       ``compile/<stage>`` spans of an active ``Tracer``

Fleet request telemetry (ISSUE 20) adds three serving-tier modules:

  :mod:`.lifecycle`    per-request ``RequestTrace`` -> the
                       ``serve_timeline/v1`` sub-doc every
                       ``serve_result``/``serve_reject`` carries
  :mod:`.slo`          windowed per-(tenant, grid, bucket) SLO burn
                       rates -> ``serve_slo/v1``
  :mod:`.flight`       fault-triggered flight recorder ->
                       ``flight_record/v1``

Scope grammar (stable; what a device trace of a COMPILED run is split by)
-------------------------------------------------------------------------
Under ``jax.jit`` nothing times a phase, but every phase still names its
ops: the scoped form always enters ``jax.named_scope`` (trace-time
metadata only; the optimized HLO is identical with and without it, once
``metadata={...}`` is stripped), and the names reach the compiled
program as ``metadata={op_name="jit(f)/.../el.cholesky/k03/update/..."}``,
where xprof / Perfetto show them and ``benchmark/scopes.py`` sums device
time by them.  A path is made of:

  ``el.<driver>``          a public driver: ``cholesky``, ``lu``, ``qr``,
                           ``gemm``, ``herk``, ``trsm``, ``svd``, ``polar``,
                           ``herm_eig`` and its three stages ``hermitian_tridiag``,
                           ``tridiag_eig``, ``apply_q_herm_tridiag``,
                           ``least_squares`` and, inside it or alone,
                           ``tsqr``, ``lu_nopiv`` (:func:`scoped`); the one-device
                           paths carry the same names
  ``k<step>/<phase>``      inside a driver: the step, two digits or more
                           (``k03``, ``k117``), then a phase of
                           :data:`PHASES` -- ``diag``, ``panel``,
                           ``swap``, ``solve``, ``spread``, ``update``,
                           ``tail`` (CALU adds ``tournament``, the serve
                           loop ``batch``, the grid Cholesky ``k00/mask``:
                           the masked copy of the operand it factors
                           in).  The Hermitian eigensolve adds six:
                           ``hermitian_tridiag`` names ``k<panel>/hemv``
                           (the matvec's operand, made once a panel,
                           then the column loop's one matvec against it:
                           on one TPU chip the slice of the trailing
                           view as stored and the ``el_symv_lower``
                           kernel, which reads its lower triangle once;
                           on a square grid of TPU chips the same, each
                           chip's kernel on its own shard inside a
                           ``shard_map``, and the ONE all-reduce that
                           joins the partial results, which reads
                           ``k<panel>/hemv/shard_map/el.redist.hemv_join``
                           and so ``redist``; elsewhere the view's mirror
                           into a full Hermitian matrix and a ``gemv``)
                           BESIDE
                           ``k<panel>/panel`` (the
                           rest of the column loop, the packed panel's
                           store) and ``k<panel>/update`` (the rank-2k
                           trailing update); ``tridiag_eig`` names
                           ``k<level>/leaf`` (level 0, the batched dense
                           leaves), ``k<level>/secular`` (the secular
                           equation, the Gu-Eisenstat weights, the fill
                           of V), ``k<level>/fill`` (once, between the
                           replicated levels and the distributed ones:
                           the zeros of the [MC,MR] eigenvector matrix
                           and, for each block of the batch, its
                           ``[STAR,STAR] -> [MC,MR]`` hand-over and the
                           ``interior_update`` that places it whole on
                           the diagonal: dense copies, no gather) and
                           ``k<level>/merge`` (the eigenvector
                           products and their stores), ``level`` counting
                           merges from the leaves up;
                           ``apply_q_herm_tridiag`` names
                           ``k<panel>/apply``.  The tall-skinny route
                           (``el.least_squares/el.tsqr`` and ``el.tsqr``
                           alone) has one step, ``k00``, and four phases:
                           ``local`` (the chip's Householder QR of the
                           rows it holds), ``tree`` (the all-gather of
                           the p R factors and the QR of their stack),
                           ``applyq`` (Q^T B from the reflectors, the
                           chip's own and then the stack's; in ``tsqr``
                           the explicit Q) and ``solve`` (R X = Y); the
                           two hops beside them read
                           ``el.redist.MC_MR.to.VC_STAR``.  A nested driver or local
                           finish nests its own (``k14/tail/k00/diag``,
                           ``k05/merge/el.gemm/k00/panel``):
                           the FIRST ``k<step>`` gives an op its phase
  ``el.hpd_solve`` /       the public solves, which open ``factor`` and
  ``el.lu_solve``          ``sweeps`` around their two stages, so a sweep
                           reads ``el.hpd_solve/sweeps/el.trsm/k02/solve``
  ``el.mixed_solve``       the mixed-precision solve (``lapack/mixed.py``):
                           ``factor`` around ``el.lu_nopiv`` (the LU
                           without pivoting: ``k<step>/diag``, ``/panel``,
                           ``/update`` on one chip, ``/panel``, ``/solve``,
                           ``/update`` on a grid), ``sweeps`` around the
                           FIRST solve's two ``el.trsm``, and ``el.refine``
                           around the refinement, whose two phases are
                           ``residual`` (``B - A X`` through a stationary-A
                           ``el.gemm``, and the norms) and ``correct`` (the
                           correction's two ``el.trsm`` and ``X += D``):
                           ``el.refine/k00/residual`` once before the
                           ``lax.while_loop`` and, opened INSIDE its body,
                           ``el.refine/while/body/k01/correct`` and
                           ``.../k01/residual``.  The first ``k<step>``
                           gives an op its phase, so the nested ``trsm``
                           and ``gemm`` read ``refine/correct`` and
                           ``refine/residual``: not ``sweep`` (no
                           ``sweeps`` segment stands over them), not
                           ``update`` or ``panel``
  ``el.svd`` / ``el.polar``  the singular value decomposition and the polar
                           decomposition it rests on (``lapack/spectral.py``,
                           ``lapack/funcs.py``).  ``el.svd`` stands around
                           everything; inside it ``el.polar`` around the
                           QDWH iteration, the consecutive steps of
                           one variant under a segment that says the
                           variant and their numbers from 01,
                           ``<steps>`` = ``<first>`` or
                           ``<first>_<last>`` (two steps or more are ONE
                           ``lax.fori_loop`` body over their scalars,
                           the segment opened inside it:
                           ``el.polar/while/body/qdwh_chol03_06/...``):
                           ``qdwh_qr<steps>`` (the QR-based form:
                           ``el.qr`` of the stack ``[sqrt(c) X; I]``,
                           ``el.thin_q``, ``el.gemm``) |
                           ``qdwh_chol<steps>`` (the Cholesky-based
                           form: ``el.herk``, ``el.cholesky``, two
                           ``el.trsm``), and
                           ``polar_h`` around ``H = U_p^H A``; then the
                           inner ``el.herm_eig`` as it is, and ``svd_u``
                           around ``U = U_p V``.  None of these segments
                           is a ``k<step>``, so the nested drivers' ops
                           keep their own phase
                           (``.../qdwh_qr01_02/el.qr/k03/panel``
                           reads ``qr/panel``), and
                           ``benchmark/svd_share.py`` reads what stands
                           over them.  The QR-based step factors its
                           stack and forms the thin Q over the rows and
                           columns that are not structurally zero
                           (``lapack/qr.py:_stack_qr_thin_q``): the
                           factorization under ``el.qr`` with
                           ``k<panel>/panel`` and ``/update`` as ``qr``'s
                           own, the backward sweep that forms
                           ``(Q1, Q2)`` under ``el.thin_q/k<panel>/apply``
                           (it read ``polar/-`` while ``apply_q``, which
                           opens no scope, formed it)
  ``el.redist.<SRC>.to.<DST>``  every public ``redistribute`` entry
                           (``el.redist.MC_MR.to.VC_STAR``), around ALL
                           it emits: the collectives and the local pack /
                           unpack / reshape / copy beside them;
                           ``el.redist.panel_spread`` and
                           ``el.redist.row_permute`` likewise, and
                           ``el.redist.hemv_join`` (the grid
                           tridiagonalization's sum of a column's
                           partial matvecs over all chips: one ``psum``
                           of a replicated vector, the reference's
                           ``Contract`` to ``[STAR,STAR]``)
  ``pack`` / ``wire`` /    under an ``el.redist.<name>``, one of
  ``unpack``               :data:`REDIST_PARTS`, opened by the engine's
                           primitives where the work is emitted
                           (:func:`redist_part`): ``wire`` around each
                           EXPLICIT collective call and nothing else
                           (``all_gather``, ``all_to_all``, ``ppermute``,
                           ``psum``, ``psum_scatter``; an async pair's
                           ``-start`` and ``-done`` inherit it);
                           ``pack`` the local ops that feed it (the pad,
                           the reshape into per-peer blocks, the local
                           transpose of a column form, the cast or
                           quantize-encode to the wire dtype, the plan
                           executor's gather by its index tables);
                           ``unpack`` the local ops after it (the
                           alignment's ``roll`` of the gathered blocks,
                           the interleave, the cyclic filter with its
                           ``optimization_barrier``, the slice to the true
                           extent, the masks, the slot permutation's
                           ``take``, the decode, the panel spread's
                           adjoint transpose, the plan executor's
                           scatter); an exchange with NO collective (a
                           pure local filter such as ``[STAR,STAR] ->
                           [MC,MR]``, a degenerate hop on a 1-wide grid)
                           is all ``unpack``.  The part of an op is the
                           FIRST of the three that stands after the first
                           ``el.redist.`` segment of its path
                           (``.../el.redist.MC_MR.to.VC_STAR/
                           jit(_redistribute_jit)/shard_map/wire/
                           all_to_all``; a chain of hops names each
                           hop's parts in turn under the one name).  An
                           op under an ``el.redist.`` name with NO part
                           is ``planned``: motion the COMPILER plans
                           (``el.redist.row_permute``: ``move_rows``,
                           ``permute_rows_storage``) and the literals it
                           materialises; ``benchmark/redist_parts.py``
                           books it as the remainder
  ``el_potrf_inv_panel`` / the ``name=`` of the Pallas kernels
  ``el_lu_panel`` /        (``kernels/``: the three panel kernels, the
  ``el_qr_panel`` /        one-pass triangle ``symv`` and the unpivoted
  ``el_symv_lower`` /      block LU), which is how a trace shows a
  ``el_lu_nopiv_block``    ``pallas_call``: the optimized HLO's
                           instruction is ``%el_symv_lower.<n>``, a
                           ``tpu_custom_call`` whose ``op_name`` ends
                           ``.../k<panel>/hemv/el_symv_lower/pallas_call``
                           (``%el_lu_nopiv_block.<n>`` under
                           ``el.lu_nopiv/k<step>/diag``: one a sub-block
                           of a diagonal block, so ``panel_share`` reads
                           it as it read the loop it replaced)

An op in an ``el.`` scope but outside any phase (a driver's final
assembly or mask) belongs to the driver; an op with no ``el.`` segment
was made by the compiler or was not named.  The persistent compile cache
is keyed with these names (``core/compile_cache.py``), so an executable
from the cache carries this build's.  Names only, with one thing to know:
XLA names an instruction it MERGES (two transposes into one) after the
tail of the merged ``op_name``, so a scope opened inside a ``shard_map``
renames a few instructions (``%transpose_transpose.84`` ->
``%transpose.353``) and shifts the numbers after them; the ops, shapes,
operands and order are the same (``perf.program_size drivers`` and
``tests/obs/test_scopes.py`` compare with the instructions renamed in
order).

Counters of the engine and the drivers, ticked where their Python runs:
every eager call, and once per trace under ``jit`` (a cached jaxpr does
not tick again).  Read them under ``metrics_scope()``:

  ``redist_unpack{impl,dim}``   one local unpack after a gather
                           (``impl`` ``tiled`` | ``generic``; ``dim`` 1 for
                           the lane dimension)
  ``redist_filter{impl,dim}``   its mirror: one local cyclic slice that
                           makes a replicated (or coarser) dimension
                           distributed (the same two labels, by the block
                           it returns)
  ``chol_update``          one trailing update of the blocked Cholesky,
                           a step of the loop, made where the trailing
                           matrix lies in the one working buffer: the
                           one-chip loop (``lapack/cholesky.py:
                           _local_chol_array``) and the grid loop, whose
                           replicated tail ticks for its own steps
  ``chol_update_stripe``   one stripe of a grid step's trailing update
                           (``lapack/cholesky.py``, the grid loop only):
                           the matmul ``L21[j:] L21H[:, j:j+q]``,
                           ``q = 2 ib``, over a column stripe of the
                           window's lower trapezoid, right of the
                           look-ahead's strip (from ``ib``; from 0
                           classic).  A window of ``j`` blocks ticks
                           ``j // 2`` times (``(j + 1) // 2`` classic):
                           240 at N = 65536, 56 at N = 32768 (ib 2048,
                           tail at 4096), where ``chol_update`` reads 30
                           and 14 before the tail
  ``row_permute{kind}``    one storage-level row permutation: ``kind``
                           ``move`` (``move_rows``: a panel step's pivot
                           swaps) | ``full`` (``permute_rows_storage``:
                           all of B); beside it, under the same label,
                           ``row_permute_rows`` (rows asked to move) and
                           ``row_permute_wire_bytes`` (worst-case bytes a
                           device receives: every row crossing chips)
  ``herm_tridiag_panel``   one panel of ``hermitian_tridiag`` (a column
                           loop and, but for the last, a rank-2k update)
  ``herm_tridiag_hemv{impl}``   one panel's choice of matvec: ``impl``
                           ``symv`` (one TPU chip, real float32: the
                           ``el_symv_lower`` kernel on the view as
                           stored; 64 at n = 16384, nb 256) |
                           ``symv_grid`` (a SQUARE grid of TPU chips,
                           real float32: the kernel's shard form on
                           each chip's shard as stored and one
                           all-reduce; 64 there too) | ``mirror``
                           (everything else, non-square grids, complex
                           entries and the CPU among it:
                           ``lapack/condense.py:_reads_triangle_once``)
  ``herm_tridiag_symmetrize``   one mirror of a panel's trailing view
                           into a full Hermitian matrix, the operand of
                           the mirror path's ``gemv``: one a panel there,
                           never one a column; none on the ``symv`` and
                           ``symv_grid`` paths
  ``dc_merge{kind}``       one merge of ``tridiag_eig``: ``kind``
                           ``replicated`` (a level of the vmapped batch
                           ticks once for each of its merges) |
                           ``distributed`` (on the [MC,MR] eigenvector
                           matrix: 31 at n = 16384 with the defaults,
                           32 subproblems of 512 up to one; a level on
                           the grid's grain is one merge in a
                           ``fori_loop`` and ticks once for each trip,
                           as every counter under it does:
                           ``metrics.repeated``)
  ``gemm_route{alg}``      one ``gemm`` on a grid of more than one
                           device, with the RESOLVED ``alg`` (``'auto'``
                           through the tuner): ``slice`` 56 and ``gspmd``
                           6 at n = 16384 on 2x2, two products for each
                           of the 31 distributed merges (levels of 512,
                           1024, 2048 take ``slice``; 4096, 8192
                           ``gspmd``)
  ``dc_fill_block``        one eigenvector block placed on the diagonal of
                           the [MC,MR] matrix at ``tridiag_eig``'s hand-off
                           (32 at n = 16384 with the defaults; none where
                           every merge is replicated or ``vectors`` is off)
  ``apply_q_panel``        one panel of ``apply_q_herm_tridiag``
  ``lu_nopiv_step``        one step of the LU without pivoting
                           (``lapack/mixed.py``: 16 at n = 32768, nb 2048)
  ``lu_nopiv_diag{impl}``  one diagonal block of it, ticked beside
                           ``lu_nopiv_step``, with the lowering its
                           sub-blocks' column recurrence took: ``impl``
                           ``kernel`` (ONE TPU chip, real float32: the
                           ``el_lu_nopiv_block`` kernel, the sub-block
                           in VMEM; 16 at n = 32768, nb 2048, 128
                           launches) | ``xla`` (everything else:
                           ``lapack/mixed.py:_diag_blocks_in_vmem``)
  ``mixed_update{dtype}``  one trailing update of it, with the dtype its
                           operands were ROUNDED to before the product
                           (``bfloat16`` in ``mixed_solve``: 15 there;
                           ``float32`` where ``lu_nopiv(low=None)`` keeps
                           them); the refinement's step count is not a
                           counter: it is decided on the device and comes
                           back as ``info["steps"]``
  ``panel_tri_product{kind}``   one product of a panel with a
                           triangular block's inverse (``lapack/lu.py:
                           _tri_matmul``: ``L21 = A21 U11^-1`` or
                           ``A21 L11^-H``, ``U12 = L11^-1 A12``):
                           ``kind`` ``blocked`` (the inverse is at least
                           two blocks of ``lu.TRI_BLOCK`` wide: the
                           contraction stops at each block's diagonal;
                           30 in ``mixed_solve`` and 15 in ``hpd_solve``
                           at n = 32768, nb 2048, one chip) | ``dense``
                           (narrower: the one matmul)
  ``svd_route{approach}``  one ``svd`` (a nested call ticks for itself:
                           the transpose of a wide operand, ``'chan'``'s
                           SVD of R) with the RESOLVED ``approach``:
                           ``polar`` | ``chan`` | ``golub`` | ``local``
                           (``'auto'`` on a square operand: ``polar``)
  ``qdwh_step{kind}``      one step of ``polar``'s QDWH iteration: ``kind``
                           ``qr`` | ``chol``; the schedule is static, from
                           the dtype's eps: 2 and 4 in float32, 2 and 6
                           in float64 (a loop body is traced once and
                           ticks for each of its trips)
  ``qdwh_stack_qr{route}``  one QR-based step's factorization of its stack
                           and thin Q, beside ``qdwh_step{kind=qr}`` and
                           weighed by the trips as it is: ``route``
                           ``structured`` (every panel at columns
                           ``[s, e)`` gathered, reduced and applied over
                           rows ``(s, m + e)``, the T of each kept for the
                           thin Q; 2 in ``svd.1x1.b2b``'s program) |
                           ``dense`` (on a grid whose grain ``m + e``
                           misses, some panel kept rows ``(s, m + n)``)
  ``polar_block{stage,nb}``  the block one stage of ``polar`` / ``svd``'s
                           polar route runs with: ``stage`` ``qr`` (the
                           QR-based steps' stack: its panels and the
                           thin Q's sweep) |
                           ``chol`` (``herk``, ``cholesky``, ``trsm`` and
                           the ``gemm``s) | ``eig`` (``svd``'s inner
                           ``herm_eig``), ``nb`` the explicit one or, with
                           ``nb=None``, what ``tune.policy.stage_blocksize``
                           picks from the shape, the grid and the dtype
                           (512, 2048, 256 at n = 16384 in float32)
  ``lstsq_route{kind}``    one ``least_squares``: ``kind`` ``tall`` (every
                           chip factors its own rows: ``lapack/qr.py:
                           _takes_tall_route``) | ``blocked`` (``qr``,
                           ``apply_q``, ``trsm``)
  ``tsqr_leaf``            one chip-local QR of the tall route (the
                           ``shard_map`` body is traced once: one a solve)
  ``tsqr_tree_bytes``      bytes of the R factors a chip receives in the
                           tree's all-gather: p n^2 itemsize (1,048,576 at
                           n = 256 on 2x2); nothing on one chip

Counters of the compile log (:mod:`.compile_log`), ticked on the current
registry whenever JAX compiles, in any mode:

  ``compile_seconds{stage}``    SELF seconds of a span of ``stage``
                           ``trace`` | ``lower`` | ``backend`` (its
                           duration less that of the spans inside it)
  ``compile_requests``     one backend compile that asked the persistent
                           cache; ``compile_cache_hits`` of them were read
                           back from it, ``compile_cache_misses`` compiled

Host spans (an active ``Tracer`` only; on the profiler's clock through
``Tracer.epoch_anchor``, and IN a running profiler's host plane as
``jax.profiler.TraceAnnotation``s): every ``Tracer.span`` by its name,
every phase of a tick channel as ``<driver>/k<step>/<phase>``, and every
record of the compile log as ``compile/<stage>`` with attrs ``fun_name``,
``cache`` (``hit`` | ``miss`` | empty), child of the span open on its
thread.

CLI: ``python -m perf.trace {run,summary,export,serve}``.
"""
from .metrics import (SCHEMA as METRICS_SCHEMA, FAMILIES as HIST_FAMILIES,
                      MetricsRegistry, REGISTRY,
                      current as current_metrics, scoped as metrics_scope,
                      hist_family, inc, observe, set_gauge,
                      set_hist_family)
from .tracer import (TRACE_SCHEMA, CommEvent, InstantEvent, NullHook,
                     NULL_HOOK, PhaseHook, PhaseRecord, Span, Tracer,
                     REDIST_PARTS, active_tracer, phase_hook, redist_part,
                     ring_bytes, scoped)
from .phase_timer import PHASES, SCHEMA as PHASE_TIMINGS_SCHEMA, PhaseTimer
from .export import (CHROME_SCHEMA, chrome_trace_doc,
                     phase_timings_to_chrome, write_json)
from . import compile_log
from .lifecycle import (SCHEMA as TIMELINE_SCHEMA, EDGES as LIFECYCLE_EDGES,
                        RequestTrace, check_timeline)
from .slo import (SCHEMA as SLO_SCHEMA, SLOMonitor, SLOTarget)
from .flight import (SCHEMA as FLIGHT_SCHEMA, FlightRecorder)

__all__ = [
    "METRICS_SCHEMA", "HIST_FAMILIES", "MetricsRegistry", "REGISTRY",
    "current_metrics", "metrics_scope", "hist_family", "inc", "observe",
    "set_gauge", "set_hist_family",
    "TRACE_SCHEMA", "CommEvent", "InstantEvent", "NullHook", "NULL_HOOK",
    "PhaseHook", "PhaseRecord", "Span", "Tracer", "active_tracer",
    "phase_hook", "ring_bytes", "scoped",
    "PHASES", "REDIST_PARTS", "redist_part", "PHASE_TIMINGS_SCHEMA",
    "PhaseTimer",
    "CHROME_SCHEMA", "chrome_trace_doc", "phase_timings_to_chrome",
    "write_json", "compile_log",
    "TIMELINE_SCHEMA", "LIFECYCLE_EDGES", "RequestTrace", "check_timeline",
    "SLO_SCHEMA", "SLOMonitor", "SLOTarget",
    "FLIGHT_SCHEMA", "FlightRecorder",
]
