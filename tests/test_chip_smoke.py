"""``chip_smoke.py``'s phases on the CPU at the smoke configuration.

The chip run drives codeqwen1.5-7b at its published widths on a TPU; here
the same phase functions run at the smoke width with Pallas kernels in
interpret mode.  The test steers the dispatch policy to "kernels" through
the config it passes in (on the CPU "auto" would take the reference
route), so the phases' own checks — no reference route, every request
served, kernel logits within tolerance of the reference route — hold
exactly as they must on the chip.
"""
import dataclasses
import importlib.util
import sys

import pytest

from helpers import REPO, run_multidevice
from repro.configs import get_arch


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def _smoke_cfg(chip_smoke):
    return dataclasses.replace(get_arch(chip_smoke.ARCH).smoke(),
                               dispatch="kernels")


def _smoke_size(chip_smoke):
    return chip_smoke.ServeSize(slots=2, max_len=64, page=8, requests=2,
                                prompt_len=20, max_new=4, rate=0.5,
                                check_steps=3)


def test_serve_phase_smoke(chip_smoke):
    lines = []
    out = chip_smoke.serve_phase(_smoke_cfg(chip_smoke),
                                 _smoke_size(chip_smoke), log=lines.append)
    routes = out["routes"]
    for op in chip_smoke.SERVE_OPS:
        assert routes.get((op, "kernel"), 0) > 0
        assert routes.get((op, "reference"), 0) == 0
    assert out["tokens"] == 2 * 4
    assert out["logits"]["worst_rel_rms"] <= chip_smoke.LOGITS_REL_RMS_TOL
    assert any(line.startswith("[serve] routes:") for line in lines)


def test_train_phase_smoke(chip_smoke):
    lines = []
    out = chip_smoke.train_phase(
        _smoke_cfg(chip_smoke),
        chip_smoke.TrainSize(layers=2, batch=2, seq=32, steps=2),
        log=lines.append)
    for op in chip_smoke.TRAIN_OPS:
        assert out["routes"].get((op, "kernel"), 0) > 0
        assert out["routes"].get((op, "reference"), 0) == 0
    assert len(out["losses"]) == 2
    assert any(line.startswith("[train] cuts:") for line in lines)


def test_four_chip_phase_smoke():
    """The ``--four-chips`` path (tp=4 over ``make_serving_mesh`` against
    tp=1) on four virtual CPU devices."""
    out = run_multidevice("""
        import dataclasses, importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", %r)
        cs = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = cs
        spec.loader.exec_module(cs)
        from repro.configs import get_arch
        cfg = dataclasses.replace(get_arch(cs.ARCH).smoke(),
                                  dispatch="kernels")
        size = cs.ServeSize(slots=2, max_len=64, page=8, requests=2,
                            prompt_len=20, max_new=4, rate=0.5,
                            check_steps=3)
        res = cs.four_chip_phase(cfg, size, tp=4)
        print("STREAMS", res["streams_identical"])
        print("WORST", res["logits"]["worst_rel_rms"])
    """ % str(REPO / "chip_smoke.py"), n_devices=4)
    assert "[tp4] routes:" in out
    assert "STREAMS 2" in out
    worst = float(out.split("WORST ")[1].split()[0])
    assert worst <= 5e-2


def test_check_routes_rejects_reference(chip_smoke):
    routes = {("matmul", "kernel"): 3, ("matmul", "reference"): 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="reference"):
        chip_smoke.check_routes(routes, ("matmul",), "probe")
    with pytest.raises(chip_smoke.SmokeFailure, match="no kernel route"):
        chip_smoke.check_routes({}, ("matmul",), "probe")


def test_compare_logits_tolerance(chip_smoke):
    import numpy as np
    rng = np.random.default_rng(0)
    want = rng.normal(size=(3, 64)).astype(np.float32)
    near = want * (1 + 1e-3)
    assert chip_smoke.compare_logits(near, want, "near")["argmax_agree"] == 3
    with pytest.raises(chip_smoke.SmokeFailure, match="disagree"):
        chip_smoke.compare_logits(want + 0.5, want, "far")


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err
