"""Environment layer: blocksize stack, Timer, CLI Args, Ctrl dataclasses.

Reference test analog: the reference exercises these through every driver
(``El::Input``/``ProcessInput`` in each test main; blocksize via
``SetBlocksize`` flags) rather than a dedicated unit file.
"""
import io
import time

import numpy as np
import pytest

import elemental_tpu as el

from ..conftest import compiled


class TestBlocksize:
    def test_default(self):
        assert el.blocksize() == 128

    def test_push_pop(self):
        el.push_blocksize(64)
        assert el.blocksize() == 64
        assert el.pop_blocksize() == 64
        assert el.blocksize() == 128

    def test_scope(self):
        with el.blocksize_scope(32):
            assert el.blocksize() == 32
            with el.blocksize_scope(16):
                assert el.blocksize() == 16
            assert el.blocksize() == 32
        assert el.blocksize() == 128

    def test_underflow_and_validation(self):
        with pytest.raises(RuntimeError):
            el.pop_blocksize()
        with pytest.raises(ValueError):
            el.set_blocksize(0)

    def test_feeds_blocked_algorithms(self, grid24):
        """nb=None resolves through the stack: a tiny blocksize must change
        the blocked-loop trip count but not the factorization result."""
        rng = np.random.default_rng(0)
        G = rng.normal(size=(24, 24))
        A = G @ G.T + 24 * np.eye(24)
        Ad = el.from_global(A, el.MC, el.MR, grid=grid24)
        with el.blocksize_scope(4):
            L4 = np.asarray(el.to_global(compiled(el.cholesky)(Ad)))
        L128 = np.asarray(el.to_global(compiled(el.cholesky)(Ad)))
        np.testing.assert_allclose(np.tril(L4), np.tril(L128), atol=1e-10)


class TestTimer:
    def test_accumulates(self):
        t = el.Timer("x")
        t.start(); time.sleep(0.01); s = t.stop()
        assert s >= 0.009 and t.total() >= 0.009
        with t:
            time.sleep(0.005)
        assert t.total() >= 0.014
        t.reset()
        assert t.total() == 0.0

    def test_misuse(self):
        t = el.Timer()
        with pytest.raises(RuntimeError):
            t.stop()
        t.start()
        with pytest.raises(RuntimeError):
            t.start()


class TestArgs:
    def test_typed_parsing(self):
        a = el.Args(["--m", "500", "--tol", "1e-6", "--upper", "--name", "hi"])
        assert a.input("--m", "height", 100) == 500
        assert a.input("--tol", "tolerance", 1e-8) == 1e-6
        assert a.input("--upper", "uplo", False) is True
        assert a.input("--name", "label", "x") == "hi"
        assert a.input("--nb", "blocksize", 128) == 128   # default
        a.process()

    def test_unknown_flag_rejected(self):
        a = el.Args(["--bogus", "1"])
        a.input("--m", "height", 100)
        with pytest.raises(ValueError, match="unknown flag"):
            a.process()

    def test_required_missing(self):
        a = el.Args([])
        a.input("--m", "height", required=True)
        with pytest.raises(ValueError, match="missing required"):
            a.process()

    def test_dashed_value_consistency(self):
        """A non-bool flag consumes the next token as its value even when it
        starts with '--'; process() must tokenize identically."""
        a = el.Args(["--name", "--weird"])
        assert a.input("--name", "label", "d") == "--weird"
        a.process()   # must not reject '--weird' as an unknown flag

    def test_report(self):
        a = el.Args(["--m", "3"])
        a.input("--m", "height", 100)
        buf = io.StringIO()
        a.print_report(stream=buf)
        assert "--m" in buf.getvalue() and "height" in buf.getvalue()


class TestCtrl:
    def test_hashable_and_kwargs(self):
        c = el.HermitianEigCtrl(vectors=False, approach="tridiag")
        assert hash(c) is not None
        kw = c.kwargs()
        assert kw == {"vectors": False, "approach": "tridiag"}

    def test_threads_into_driver(self, grid24):
        rng = np.random.default_rng(1)
        A = rng.normal(size=(16, 16))
        A = A + A.T
        Ad = el.from_global(A, el.MC, el.MR, grid=grid24)
        c = el.HermitianEigCtrl(vectors=False, approach="tridiag", nb=8)
        w = el.herm_eig(Ad, **c.kwargs())
        np.testing.assert_allclose(np.sort(np.asarray(w)),
                                   np.linalg.eigvalsh(A), atol=1e-8)


class TestProgressLog:
    def test_records(self):
        p = el.ProgressLog("ipm")
        p.log(0, gap=1.0); p.log(1, gap=0.1)
        assert p.history("gap") == [1.0, 0.1]
