"""The census of options: every keyword of the public entries, pinned.

ROADMAP D1 asks each PR to count options before and after.  This is that
count, kept by the suite: the sets below are the signatures as PR 33
found them (ROADMAP D16 has the numbers), and D1's later PRs shrink them.
"""
import inspect

import pytest

import elemental_tpu as el

CENSUS = {
    "lu": {"nb", "precision", "update_precision", "lookahead", "crossover",
           "panel", "panel_impl", "inners", "comm_precision", "redist_path",
           "timer", "health", "abft"},
    "cholesky": {"uplo", "nb", "precision", "lookahead", "crossover",
                 "panel_impl", "comm_precision", "redist_path", "timer",
                 "health", "abft"},
    "qr": {"nb", "precision", "panel", "panel_impl", "comm_precision",
           "timer", "health", "redist_path", "abft"},
    "gemm": {"alpha", "beta", "C", "orient_a", "orient_b", "alg", "nb",
             "precision", "comm_precision", "redist_path"},
    "trsm": {"alpha", "unit", "nb", "precision", "comm_precision",
             "redist_path"},
    "herk": {"alpha", "beta", "C", "orient", "nb", "precision", "conj",
             "comm_precision", "redist_path"},
    "hpd_solve": {"uplo", "nb", "precision", "info", "health"},
    "lu_solve": {"nb", "precision", "panel", "info", "health"},
    "mixed_solve": {"nb", "precision", "max_steps"},
    "least_squares": {"nb", "precision", "abft"},
    "redistribute": {"calign", "ralign", "comm_precision", "path"},
}


@pytest.mark.parametrize("entry", sorted(CENSUS))
def test_keywords_of_a_public_entry_are_the_census(entry):
    params = inspect.signature(getattr(el, entry)).parameters.values()
    assert not any(p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
                   for p in params), f"el.{entry} hides options in * or **"
    keywords = {p.name for p in params if p.default is not p.empty}
    new = sorted(keywords - CENSUS[entry])
    assert not new, (f"el.{entry} takes {new}: a new option: say in "
                     f"ROADMAP D1 which one it replaces")
    gone = sorted(CENSUS[entry] - keywords)
    assert not gone, (f"el.{entry} no longer takes {gone}: take them out "
                      f"of the census and lower the count in ROADMAP D16")
