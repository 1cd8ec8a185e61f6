"""``chip_smoke.py``'s control flow, on the CPU at tiny size.

The script is the standing proof that the main path runs on the chip;
here only its two behaviours a sandbox can show: with no accelerator it
stops at the device phase without training anything, and — with that
one check stood in for, in the test — its train and serve phases run
to the last line, and so does the ``--chips 4`` phase on four virtual
CPU devices. ``--tiny`` only shrinks shapes; every phase and every
check is the one the chip run makes.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("device_check",
                         ["real", "stood_in", "stood_in_four"])
def test_chip_smoke_control_flow(device_check, monkeypatch, capsys):
    import jax

    smoke = _load_chip_smoke()
    trained = []
    real_fit = smoke.fit_plain
    monkeypatch.setattr(
        smoke, "fit_plain",
        lambda *a, **k: trained.append(1) or real_fit(*a, **k),
    )
    if device_check == "real":
        # this sandbox has no accelerator: non-zero exit at the device
        # phase, nothing trained, no result line
        with pytest.raises(SystemExit) as exc:
            smoke.main(["--tiny"])
        assert exc.value.code not in (0, None)
        assert "no accelerator" in str(exc.value.code)
        assert not trained
        assert '"ok"' not in capsys.readouterr().out
        return
    monkeypatch.setattr(smoke, "check_device",
                        lambda chips: jax.devices()[:chips])
    if device_check == "stood_in_four":
        # at 32x32 / batch 8 the last stage's BatchNorm sees 8 values a
        # channel and bf16 rounding swings the trajectory; the stated
        # tolerance belongs to the full-width shapes (0.8% there on
        # four virtual devices), so only the control flow is held here
        monkeypatch.setattr(smoke, "DP_LOSS_RTOL", 1.0)
        assert smoke.main(["--tiny", "--chips", "4"]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert {ln.split("]")[0] for ln in lines[:-1]} == {
            "[device", "[dp"}  # no other phase
        shards = json.loads(
            next(ln for ln in lines if "batch_leaf_shards" in ln)
            .split("] ", 1)[1]
        )
        assert shards["params_device_set"] == 4
        assert shards["batch_leaf_shards"] == 4
        assert shards["all_reduce_ops"] >= 1
        assert json.loads(lines[-1])["device"]["count"] == 4
        return
    assert smoke.main(["--tiny", "--seed", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert trained
    phases = [ln.split("]")[0].lstrip("[") for ln in lines[:-1]]
    assert phases[0] == "device"
    assert "train" in phases and "serve" in phases
    train = json.loads(
        next(ln for ln in lines if ln.startswith("[train]"))
        .split("] ", 1)[1]
    )
    assert train["optimizer_steps"] >= 8
    assert train["losses"][-1] < train["losses"][0]
    serve = json.loads(
        next(ln for ln in lines if ln.startswith("[serve]"))
        .split("] ", 1)[1]
    )
    assert serve["post_warmup_compiles_total"] == 0
    assert serve["warmup_predicts_total"] >= 1
    # the contract's last line, and nothing else on it
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1,
    }}
