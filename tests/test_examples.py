"""Examples double as a smoke suite (the reference's examples/** role,
SURVEY.md §5): every driver runs on the virtual mesh at a tiny size and
its reported residuals/convergence are checked, not just exit status.
"""
import os
import runpy
import sys

import pytest

_EX = os.path.join(os.path.dirname(__file__), os.pardir, "examples")

_CASES = [
    ("cholesky.py", ["--n", "96"], ["factor_resid", "solve_resid"]),
    ("lu.py", ["--n", "96"], ["factor_resid"]),
    ("mixed_solve.py", ["--n", "192", "--nb", "64"],
     ["backward_error", "unrefined"]),
    ("qr_least_squares.py", ["--m", "120", "--n", "40"], ["lstsq_err"]),
    ("tall_least_squares.py", ["--m", "262144", "--n", "4"],
     ["lstsq_err", "tall"]),
    ("herm_eig.py", ["--n", "80"], ["resid", "orth"]),
    ("svd.py", ["--m", "90", "--n", "40"], ["reconstruct", "sv_err"]),
    ("lp.py", ["--m", "10", "--n", "24"], ["rel_gap"]),
    ("lav.py", ["--m", "120", "--n", "20", "--nnz", "800"],
     ["recovery_err"]),
    ("rpca.py", ["--m", "40", "--n", "40", "--rank", "2"],
     ["recovery_err"]),
    ("pseudospectra.py", ["--n", "40", "--npts", "6"], []),
    ("spd_scaling_sweep.py", ["--n", "64"], ["resid"]),
]


#: the two heaviest example scripts ride the slow tier (they exercise
#: svd/schur stacks already covered by their own lapack suites).
_SLOW_EXAMPLES = {"rpca.py", "pseudospectra.py"}


@pytest.mark.parametrize(
    "script,argv,metrics",
    [pytest.param(*c, id=c[0],
                  marks=(pytest.mark.slow,) if c[0] in _SLOW_EXAMPLES
                  else ()) for c in _CASES])
def test_example(script, argv, metrics, capsys):
    old_argv = sys.argv
    sys.argv = [script] + argv
    sys.path.insert(0, _EX)
    try:
        runpy.run_path(os.path.join(_EX, script), run_name="__main__")
    finally:
        sys.argv = old_argv
        sys.path.remove(_EX)
    out = capsys.readouterr().out
    assert "[" in out, out
    for key in metrics:
        assert f"{key}=" in out, (key, out)
        val = out.split(f"{key}=")[1].split()[0].rstrip(")")
        if val not in ("True", "False"):
            assert abs(float(val)) < 1e-3, (key, val, out)
        if key == "tall":
            assert val == "True", out
    if "converged=" in out:
        assert "converged=True" in out, out
