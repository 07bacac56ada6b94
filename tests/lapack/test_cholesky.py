"""Cholesky / HPDSolve residual oracles.

Mirrors the reference's ``tests/lapack_like/Cholesky.cpp``: factor a
known-conditioned HPD matrix (HermitianUniformSpectrum), check
  ||A - L L^H||_F / ||A||_F  and solve residuals  ||A X - B|| / ||B||.
"""
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.matrices import hermitian_uniform_spectrum

from ..conftest import compiled


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_cholesky_residual(grid24, uplo, dtype):
    n = 28
    A = hermitian_uniform_spectrum(n, 1, 10, grid24, dtype=dtype, seed=3)
    F = np.asarray(to_global(A))
    L = compiled(el.cholesky, uplo=uplo, nb=8)(A)
    Lh = np.asarray(to_global(L))
    if uplo == "L":
        assert np.allclose(np.triu(Lh, 1), 0)
        resid = np.linalg.norm(F - Lh @ Lh.conj().T) / np.linalg.norm(F)
    else:
        assert np.allclose(np.tril(Lh, -1), 0)
        resid = np.linalg.norm(F - Lh.conj().T @ Lh) / np.linalg.norm(F)
    assert resid < 1e-13


def test_cholesky_reads_only_triangle(grid42):
    n = 16
    A = hermitian_uniform_spectrum(n, 1, 5, grid42, dtype=np.float64, seed=4)
    F = np.asarray(to_global(A))
    garbage = F + np.triu(np.random.default_rng(0).normal(size=(n, n)), 1)
    Ld = compiled(el.cholesky, uplo="L", nb=8)(
        from_global(garbage, MC, MR, grid42))
    want = np.linalg.cholesky(F)
    np.testing.assert_allclose(np.asarray(to_global(Ld)), want, rtol=1e-10)


def test_cholesky_two_grids_ragged(two_grids):
    n = 19     # deliberately not a multiple of any grid dim
    A = hermitian_uniform_spectrum(n, 1, 4, two_grids, dtype=np.float64, seed=5)
    F = np.asarray(to_global(A))
    L = np.asarray(to_global(compiled(el.cholesky, nb=8)(A)))
    assert np.linalg.norm(F - L @ L.T) / np.linalg.norm(F) < 1e-13


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_hpd_solve(grid24, uplo):
    n, nrhs = 24, 7
    A = hermitian_uniform_spectrum(n, 1, 8, grid24, dtype=np.complex128, seed=6)
    F = np.asarray(to_global(A))
    rng = np.random.default_rng(7)
    B = rng.normal(size=(n, nrhs)) + 1j * rng.normal(size=(n, nrhs))
    X = compiled(el.hpd_solve, uplo=uplo, nb=8)(
        A, from_global(B, MC, MR, grid24))
    Xh = np.asarray(to_global(X))
    assert np.linalg.norm(F @ Xh - B) / np.linalg.norm(B) < 1e-12


def test_cholesky_solve_after(grid24):
    n, nrhs = 20, 3
    A = hermitian_uniform_spectrum(n, 1, 6, grid24, dtype=np.float64, seed=8)
    F = np.asarray(to_global(A))
    L = compiled(el.cholesky, nb=8)(A)
    B = np.random.default_rng(9).normal(size=(n, nrhs))
    X = compiled(el.cholesky_solve_after, nb=8)(
        L, from_global(B, MC, MR, grid24))
    assert np.linalg.norm(F @ np.asarray(to_global(X)) - B) < 1e-11 * np.linalg.norm(B)


def _grid22():
    import jax
    return el.Grid(jax.devices()[:4], height=2)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_cholesky_upper_multigrid(two_grids, dtype):
    """uplo='U' across the generic + degenerate grid sweep (the adjoint
    round-trip exercises the transpose-exchange chains per grid shape)."""
    n = 21
    A = hermitian_uniform_spectrum(n, 1, 9, two_grids, dtype=dtype, seed=13)
    F = np.asarray(to_global(A))
    U = np.asarray(to_global(compiled(el.cholesky, uplo="U", nb=8)(A)))
    assert np.allclose(np.tril(U, -1), 0)
    assert np.linalg.norm(F - U.conj().T @ U) / np.linalg.norm(F) < 1e-13


def test_cholesky_upper_2x2_grid():
    n = 24
    g = _grid22()
    A = hermitian_uniform_spectrum(n, 1, 10, g, dtype=np.complex128, seed=14)
    F = np.asarray(to_global(A))
    U = np.asarray(to_global(compiled(el.cholesky, uplo="U", nb=8)(A)))
    assert np.allclose(np.tril(U, -1), 0)
    assert np.linalg.norm(F - U.conj().T @ U) / np.linalg.norm(F) < 1e-13


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_hpd_solve_2x2_grid(uplo):
    n, nrhs = 20, 5
    g = _grid22()
    A = hermitian_uniform_spectrum(n, 1, 8, g, dtype=np.float64, seed=15)
    F = np.asarray(to_global(A))
    B = np.random.default_rng(16).normal(size=(n, nrhs))
    X = compiled(el.hpd_solve, uplo=uplo, nb=8)(A, from_global(B, MC, MR, g))
    assert np.linalg.norm(F @ np.asarray(to_global(X)) - B) \
        < 1e-12 * np.linalg.norm(B)


@pytest.mark.parametrize("n,dtype", [(24, np.float64), (19, np.complex128)])
def test_cholesky_lookahead_matches_classic(grid24, n, dtype):
    """The pipelined schedule reorders ops but computes the same update
    matmuls element-for-element: factors must agree with the classic
    right-looking driver to roundoff (crossover disabled so both run the
    full distributed loop)."""
    A = hermitian_uniform_spectrum(n, 1, 10, grid24, dtype=dtype, seed=17)
    La = compiled(el.cholesky, nb=8, lookahead=True, crossover=0)(A)
    Lb = compiled(el.cholesky, nb=8, lookahead=False)(A)
    np.testing.assert_allclose(np.asarray(to_global(La)),
                               np.asarray(to_global(Lb)),
                               rtol=1e-12, atol=1e-13)


def test_cholesky_lookahead_matches_classic_local():
    """Same agreement on the sequential (1x1 grid) fast path."""
    import jax
    g1 = el.Grid([jax.devices()[0]])
    for n in (40, 37):
        A = hermitian_uniform_spectrum(n, 1, 10, g1, dtype=np.float64,
                                       seed=18)
        La = compiled(el.cholesky, nb=16, lookahead=True)(A)
        Lb = compiled(el.cholesky, nb=16, lookahead=False)(A)
        np.testing.assert_allclose(np.asarray(La.local),
                                   np.asarray(Lb.local),
                                   rtol=1e-12, atol=1e-13)


def _shrinking_chol_panels(a, n, ib, precision, lookahead):
    """``_local_chol_array`` as it stood before ISSUE 34, plain: the
    trailing matrix copied into a smaller array at every step
    (``T = T[w:, w:]``), the finished panels kept in a list (assembled by
    ``_shrinking_chol_reference``).  The same matmuls on the same operands
    in the same order as the one-buffer loop, so the lower triangles agree
    to the bit."""
    import jax.numpy as jnp
    from elemental_tpu.lapack.cholesky import _potrf_inv
    dt, q, panels, T = a.dtype, 2 * ib, [], a

    def diag_and_panel(src, w, below):
        L11, Li11 = _potrf_inv(src[:w, :w], precision)
        L21 = (jnp.matmul(src[w:, :w], jnp.conj(Li11).T,
                          precision=precision).astype(dt) if below else None)
        return L11, L21

    nxt = diag_and_panel(T, min(ib, n), ib < n) if lookahead else None
    for s in range(0, n, ib):
        w = min(ib, n - s)
        L11, L21 = nxt if lookahead else diag_and_panel(T, w, s + w < n)
        if s + w == n:
            panels.append(L11)
            break
        panels.append(jnp.concatenate([L11, L21], axis=0))
        T = T[w:, w:]
        mt = T.shape[0]
        w2 = min(ib, mt) if lookahead else 0
        if lookahead:
            strip = T[:, :w2] - jnp.matmul(
                L21, jnp.conj(L21[:w2, :]).T, precision=precision).astype(dt)
            nxt = diag_and_panel(strip, w2, w2 < mt)
            T = T.at[:, :w2].set(strip)
        for i in range(w2, mt, q):
            iq = min(i + q, mt)
            upd = jnp.matmul(L21[i:iq, :], jnp.conj(L21[w2:iq, :]).T,
                             precision=precision)
            T = T.at[i:iq, w2:iq].set(T[i:iq, w2:iq] - upd.astype(dt))
    return panels


def _shrinking_chol_reference(a, n, ib, precision, lookahead):
    """The panels of the loop above, run as one compiled program, put where
    they belong in an n x n matrix on the host."""
    panels = compiled(_shrinking_chol_panels, n=n, ib=ib, precision=precision,
                      lookahead=lookahead)(a)
    out = np.zeros((n, n), a.dtype)
    for s, P in zip(range(0, n, ib), panels):
        out[s:, s:s + P.shape[1]] = np.asarray(P)
    return out


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("lookahead", [True, False],
                         ids=["lookahead", "classic"])
@pytest.mark.parametrize("n", [16, 17, 48, 55, 80],
                         ids=["ib", "ib+1", "3ib", "3ib+7-ragged", "5ib"])
def test_local_chol_array_one_buffer(n, lookahead, dtype):
    """The one-chip blocked loop factors in ONE n x n buffer (ISSUE 34):
    against ``numpy.linalg.cholesky`` in double precision, and the lower
    triangle equal TO THE BIT to the shrinking loop it replaced; above
    the diagonal exact zeros whatever the operand held there (each panel
    is written with zeros above it, so no caller masks the whole)."""
    import jax
    from elemental_tpu.lapack.cholesky import _local_chol_array
    ib, hi = 16, jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(34 + n)
    G = rng.normal(size=(n, n))
    if np.issubdtype(dtype, np.complexfloating):
        G = G + 1j * rng.normal(size=(n, n))
    F = G @ G.conj().T / n + 2 * np.eye(n)
    # only the lower triangle is valid input: NaN above the diagonal
    a = jax.numpy.asarray(
        (np.tril(F) + np.triu(np.full((n, n), np.nan), 1)).astype(dtype))
    got = np.asarray(compiled(_local_chol_array, n=n, ib=ib, precision=hi,
                              lookahead=lookahead)(a))
    assert not np.triu(got, 1).any()
    want = np.linalg.cholesky(F)
    assert got.dtype == dtype
    assert np.linalg.norm(got - want) < 50 * np.finfo(dtype).eps * n \
        * np.linalg.norm(want)
    ref = np.tril(_shrinking_chol_reference(a, n, ib, hi, lookahead))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("lookahead", [True, False],
                         ids=["lookahead", "classic"])
def test_local_chol_array_never_slices_the_trailing_matrix(lookahead):
    """No ``slice`` in the loop's jaxpr returns a block with both
    dimensions over ``n - 2 ib``: the trailing matrix is addressed where
    it lies, never copied into a smaller array (41.6 GB a solve at
    N = 32768; PERF.md 6, PR 34).  The compiled program's own copies are
    counted for a described chip in ``tests/test_chip_compile.py``."""
    import jax
    from elemental_tpu.lapack.cholesky import _local_chol_array
    from elemental_tpu.obs import metrics
    n, ib = 128, 16
    a = jax.ShapeDtypeStruct((n, n), np.float32)
    with metrics.scoped() as reg:
        jaxpr = jax.make_jaxpr(lambda x: _local_chol_array(
            x, n, ib, jax.lax.Precision.HIGHEST, lookahead=lookahead))(a)
    assert sum(reg.counters("chol_update").values()) == n // ib - 1

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)
    big = [e.outvars[0].aval.shape for e in eqns(jaxpr.jaxpr)
           if e.primitive.name in ("slice", "dynamic_slice")
           and min(e.outvars[0].aval.shape) > n - 2 * ib]
    assert not big, big


_WARM_CACHE_SCRIPT = r"""
import json, os, sys
import jax
jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_enable_x64", True)
from elemental_tpu.core.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import numpy as np, jax.numpy as jnp
import elemental_tpu as el
from elemental_tpu.lapack.cholesky import _pin_column_major
n, nb = 48, 16
G = np.random.default_rng(0).normal(size=(n, n)).astype(np.float32)
S = G @ G.T + n * np.eye(n, dtype=np.float32)
meta = el.from_global(jnp.asarray(S), el.MC, el.MR,
                      grid=el.Grid(jax.devices()[:1]))
F = meta.local
fns = {"cholesky": (lambda a: el.cholesky(meta.with_local(a), nb=nb).local,
                    np.linalg.cholesky(S.astype(np.float64))),
       "pin": (_pin_column_major, S)}
out = {}
for name, (fn, want) in fns.items():
    got = {"eager": fn(F), "vmap": jax.vmap(fn)(F[None])[0],
           "jvp": jax.jvp(fn, (F,), (jnp.zeros_like(F),))[0],
           "checkpoint": jax.checkpoint(fn)(F),
           "jit_vmap": jax.jit(jax.vmap(fn))(F[None])[0]}
    for how, val in got.items():
        out[name + "." + how] = float(np.abs(np.asarray(val) - want).max())
out["entries"] = len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"]))
print("RESULT " + json.dumps(out))
"""
_WARM_CACHE_RUNS = {}


def _warm_cache_runs(tmp_path_factory):
    """The script above run in two fresh processes against ONE empty
    persistent compile cache: the first fills it, the second is served
    from it.  Once a worker."""
    if not _WARM_CACHE_RUNS:
        import json, os, subprocess, sys
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                   JAX_COMPILATION_CACHE_DIR=str(
                       tmp_path_factory.mktemp("warm_cache")))
        for run in ("cold", "warm"):
            p = subprocess.run([sys.executable, "-c", _WARM_CACHE_SCRIPT],
                               capture_output=True, text=True, cwd=repo,
                               env=env, timeout=600)
            assert p.returncode == 0, p.stderr[-2000:]
            line = [ln for ln in p.stdout.splitlines()
                    if ln.startswith("RESULT ")][-1]
            _WARM_CACHE_RUNS[run] = json.loads(line[len("RESULT "):])
    return _WARM_CACHE_RUNS


@pytest.mark.parametrize("how", ["eager", "vmap", "jvp", "checkpoint",
                                 "jit_vmap"])
@pytest.mark.parametrize("what", ["cholesky", "pin"])
def test_factor_is_right_from_a_warm_compile_cache(tmp_path_factory, what,
                                                   how):
    """The one-chip driver, and the layout pin it applies on the TPU,
    called eagerly under jax's transformations in a process that finds
    every executable in the persistent compile cache (as a second run of
    the tests or of the benchmark does).  jax 0.9.0 hands an executable
    whose RESULT carries a layout back from that cache without it, and an
    eager ``with_layout_constraint`` makes just such a one: under an eager
    ``vmap`` the factor came back TRANSPOSED in the second process
    (REVIEW of PR 34).  The pin is a ``jit`` of its own, so the constraint
    is never a program's result."""
    runs = _warm_cache_runs(tmp_path_factory)
    assert runs["cold"]["entries"] > 0
    assert runs["warm"]["entries"] == runs["cold"]["entries"]   # all served
    for run in ("cold", "warm"):
        assert runs[run][f"{what}.{how}"] < (1e-5 if what == "cholesky"
                                             else 1e-30), runs[run]


def test_cholesky_crossover_boundary(grid24):
    """Tail crossover at thresholds just below / at / above the remaining
    trailing sizes (n=24, nb=8 leaves tails of 16 then 8): every setting
    must agree with the never-crossing classic factor to roundoff."""
    n = 24
    A = hermitian_uniform_spectrum(n, 1, 10, grid24, dtype=np.float64,
                                   seed=19)
    F = np.asarray(to_global(A))
    ref = np.asarray(to_global(compiled(el.cholesky, nb=8,
                                        lookahead=False)(A)))
    for xo in (7, 8, 16, n):
        L = np.asarray(to_global(compiled(el.cholesky, nb=8,
                                          crossover=xo)(A)))
        np.testing.assert_allclose(L, ref, rtol=1e-12, atol=1e-13)
        assert np.linalg.norm(F - L @ L.T) / np.linalg.norm(F) < 1e-13


@pytest.mark.parametrize("lookahead", [True, False])
def test_cholesky_panel_chain_uses_fused_spread(grid24, lookahead):
    """The [MC,STAR]/[STAR,MR] trailing-update pair must come from the ONE
    collective panel_spread fast path -- not from the three-redistribute
    chain it replaced (pinned via the engine's scoped trace-time call
    counts)."""
    from elemental_tpu.redist.engine import redist_counts
    from elemental_tpu import VC, STAR, MR
    n, nb = 32, 8
    A = hermitian_uniform_spectrum(n, 1, 10, grid24, dtype=np.float64,
                                   seed=20)
    F = np.asarray(to_global(A))
    with redist_counts() as counter:
        L = el.cholesky(A, nb=nb, lookahead=lookahead, crossover=0)
    counts = dict(counter)
    npanels = n // nb
    assert counts.get("panel_spread") == npanels - 1
    assert ((VC, STAR), (MC, STAR)) not in counts
    assert ((STAR, VC), (STAR, MR)) not in counts
    Lh = np.asarray(to_global(L))
    assert np.linalg.norm(F - Lh @ Lh.T) / np.linalg.norm(F) < 1e-13


def test_matrix_gallery(grid24):
    from elemental_tpu.matrices import identity, ones, hilbert, lehmer, minij
    n = 11
    np.testing.assert_allclose(np.asarray(to_global(identity(n, grid=grid24))), np.eye(n))
    np.testing.assert_allclose(np.asarray(to_global(ones(n, grid=grid24))), np.ones((n, n)))
    H = np.asarray(to_global(hilbert(n, grid24)))
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    np.testing.assert_allclose(H, 1.0 / (i + j + 1))
    np.testing.assert_allclose(np.asarray(to_global(lehmer(n, grid24))),
                               (np.minimum(i, j) + 1.0) / (np.maximum(i, j) + 1.0))
    np.testing.assert_allclose(np.asarray(to_global(minij(n, grid24))),
                               np.minimum(i, j) + 1.0)
