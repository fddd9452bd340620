"""chip_smoke.py on the CPU: it must refuse to run here, and its legs —
importable, sized by argument — are rehearsed at ``size_name="tiny"`` with
interpreter kernels, so the script the driver runs on the chip cannot rot
between chip runs."""

import json
import os
import subprocess
import sys

import pytest

REPO = __file__.rsplit("/tests/", 1)[0]
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = dict(size_name="tiny", vocab_size=256, seq_len=128)


def test_chip_smoke_refuses_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout  # no result line
    reason = out.stderr.strip().splitlines()[-1]
    assert "needs a TPU" in reason and "'cpu'" in reason, out.stderr[-500:]


def test_result_line_has_the_contract_keys_only(devices):
    """The driver refuses a last line with any key beyond these."""
    line = json.loads(json.dumps(chip_smoke.result_line(devices)))
    assert line == {
        "ok": True,
        "device": {
            "platform": "cpu",
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }
    assert isinstance(line["device"]["kind"], str)


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.trainer_leg(
        **TINY, batch=2, device="cpu", steps_4call=2, steps_fused=1,
        serve_config=chip_smoke.serve_config_for(TINY["seq_len"], 32),
    )


def test_trainer_leg_tiny(trained):
    stoke, _, info = trained
    assert info["optimizer_steps"] == 3 and info["backward_steps"] == 6
    assert info["losses"][-2] < info["losses"][0]
    # the CPU rehearsal interprets the kernels: no Mosaic call to find
    assert info["mosaic_program"] is None
    assert stoke.world_size == 1


def test_server_leg_tiny(trained):
    stoke, model, _ = trained
    _, info = chip_smoke.server_leg(
        stoke, model, prompt_lens=(20, 70, 20),
        vocab_size=TINY["vocab_size"], seq_len=TINY["seq_len"],
        max_new_tokens=4,
    )
    assert info["tokens_out"] == 12 and info["prefills"] == 3
    assert info["worst_logit_gap_frac"] <= chip_smoke.LOGIT_TOL_FRAC


def test_kernel_leg_tiny():
    info = chip_smoke.kernel_leg(
        heads=2, head_dim=64, seq_len=128, short_len=96, interpret=True
    )
    assert set(info) == {
        "flash_L128", "flash_L96",
        "paged_decode_float32", "paged_verify_float32",
        "paged_decode_bfloat16", "paged_verify_bfloat16",
    }


def test_sharded_leg_tiny(devices):
    stoke, info = chip_smoke.sharded_leg(
        **TINY, batch=2, device="cpu", large_leaf_elems=1 << 14
    )
    assert stoke.world_size == len(devices) == info["world_size"]
    assert info["large_leaves_sharded"] > 0
