"""CPU rehearsal of ``chip_smoke.py``: every phase function called
directly at a small size on the virtual mesh (the script itself has no
CPU branch and no option that makes one), plus the refusal to run
without a TPU."""
import json

import jax
import numpy as np
import pytest

import chip_smoke
import elemental_tpu as el


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_library_phase_small(capsys):
    chip_smoke.phase_library(el.Grid([jax.devices()[0]]), n=96, n_lu=80,
                             nrhs=3, ls_shape=(160, 24), nb=32)
    lines = _lines(capsys)
    assert [ln["op"] for ln in lines] == ["hpd_solve", "lu_solve",
                                          "least_squares"]
    assert all(ln["smoke"] and ln["backward_error"] <= ln["tol"]
               for ln in lines)


def test_library_phase_fails_on_wrong_answer(monkeypatch):
    # a solve that returns garbage must fail the phase, not print and pass
    monkeypatch.setattr(el, "hpd_solve", lambda A, B, **kw: B)
    with pytest.raises(AssertionError, match="backward error"):
        chip_smoke.phase_library(el.Grid([jax.devices()[0]]), n=64, n_lu=64,
                                 nrhs=2, ls_shape=(80, 16))


def test_kernels_phase_small(capsys):
    # interpret mode has no tpu_custom_call to find; on the chip the
    # script demands one, and that demand is rehearsed on its own below
    chip_smoke.phase_kernels(el.Grid([jax.devices()[0]]), n=96, nb=32,
                             expect_custom_call=False)
    lines = _lines(capsys)
    assert [ln["op"] for ln in lines] == ["lu", "cholesky", "qr"]
    assert not any(ln["tpu_custom_call"] for ln in lines)


def test_kernels_phase_demands_a_compiled_kernel():
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_kernels(el.Grid([jax.devices()[0]]), n=64, nb=32)


def test_serving_phase_small(capsys):
    chip_smoke.phase_serving(jax.devices()[:1], sizes=(24, 40))
    (line,) = _lines(capsys)
    passes = line["passes"]
    assert passes["cold"]["ok"] == passes["cold"]["requests"] == 6
    assert passes["cold"]["exec_compiles"] == 6
    assert passes["again"]["exec_compiles"] == 0
    assert passes["burst"]["ok"] == passes["burst"]["requests"] == 12
    assert line["leaked_threads"] == []


def test_complex_phase_small(capsys):
    chip_smoke.phase_complex(el.Grid([jax.devices()[0]]), n=48, nrhs=2)
    (line,) = _lines(capsys)
    assert line["dtype"] == "complex64"


def test_four_chip_phase_on_four_virtual_devices(capsys):
    chip_smoke.phase_four_chips(jax.devices()[:4], n=96, n_lu=96, nrhs=3,
                                nb=32)
    lines = _lines(capsys)
    placed = [ln for ln in lines if ln.get("grid") == [2, 2]]
    assert len(placed) == 2
    for ln in placed:
        assert sorted(p["device"] for p in ln["placement"]) == [0, 1, 2, 3]
        assert {p["shard_bytes"] for p in ln["placement"]} == {96 * 96}
    assert all(sum(ln["collectives"].values()) > 0 for ln in placed)
    diffs = [ln for ln in lines if "solution_diff" in ln]
    assert [ln["op"] for ln in diffs] == ["hpd_solve", "lu_solve"]


def test_same_seed_same_matrix_on_any_grid():
    g4 = el.Grid(jax.devices()[:4])
    g1 = el.Grid(jax.devices()[:1])
    for gen in (lambda g: chip_smoke.gen_hpd(40, g, 3),
                lambda g: chip_smoke.gen_general(40, 24, g, 3)):
        a4 = np.asarray(el.to_global(gen(g4)))
        a1 = np.asarray(el.to_global(gen(g1)))
        assert (a4 == a1).all()
        assert abs(a1).max() > 0.5


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(last)
    assert doc["ok"] is False and doc["device"]["platform"] == "cpu"
    assert chip_smoke.main(["--chips", "4"]) != 0
