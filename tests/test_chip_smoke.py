"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The script itself only ever runs on a TPU. Here its phase functions run at a
tiny width with the device check patched INSIDE the test (the script has no
option for that), and the unpatched script is shown to refuse a CPU.
"""

import json
import pathlib
import subprocess
import sys

import jax

from tests._helpers import subprocess_env

REPO = pathlib.Path(__file__).resolve().parent.parent

# d32/2L stand-in for mpt-125m; XLA attention and the gather serving path,
# because the Pallas kernels exist on the chip only
TINY_SETS = (
    "model.d_model=32", "model.n_layers=2", "model.n_heads=2",
    "model.max_seq_len=64", "model.vocab_size=96", "model.attn_impl=xla",
    "model.compute_dtype=float32", "train.global_batch_size=4",
    "photon.serve.n_slots=2", "photon.serve.block_size=4",
    "photon.serve.attention_impl=gather",
)


def test_phases_run_in_order_and_serve_loads_the_train_checkpoint(
        tmp_path, monkeypatch, capsys):
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "require_tpu", lambda n: jax.devices()[:n])
    monkeypatch.setattr(chip_smoke, "MAX_NEW", 4)
    run = chip_smoke.Run(tmp_path, chip_smoke.require_tpu(1), 0,
                         chip_smoke.BackendCompileClock())
    cfg = chip_smoke.phase_train(run, (*chip_smoke.TRAIN_SETS, *TINY_SETS))
    assert (tmp_path / "fed" / "config.yaml").is_file()
    chip_smoke.phase_serve(run, cfg, prompt_lens=(5, 11, 23))

    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert [l["phase"] for l in lines] == ["train", "serve"]
    train, serve = lines
    assert train["rounds"] == 2 and len(train["losses"]) == 2
    assert not train["pallas_in_step"]  # CPU: no kernel, and none was asked for
    # the serve phase served the round the train phase checkpointed
    assert serve["round"] == 2 and serve["requests"] == 6
    assert serve["prefill_logits_max_abs_err"] < chip_smoke.LOGIT_TOL
    for line in lines:
        assert line["tokens_per_s_label"] == chip_smoke.NOT_A_MEASUREMENT


def test_unpatched_script_refuses_a_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--out", str(tmp_path)],
        env=subprocess_env(), cwd=str(REPO), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    assert not any(tmp_path.iterdir())  # it stopped before any phase
