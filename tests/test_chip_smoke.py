"""chip_smoke.py cannot rot between chip runs: its stage functions run
here at toy size on the CPU (kernels in interpret mode, the dist stage
on the 8-device virtual mesh), and the script itself must refuse a
machine without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(chip_smoke.CONFIG1, scale=0.002, hidden=16, fanout=(3, 2, 2),
           batch=32, frontier_cap=64, group=2, groups=2, eager_batches=2,
           serving_requests=(1, 3, 5), seed_buckets=(4, 8), dist_steps=2,
           probe_matmul_n=128, probe_matmul_chain=2, dq_rows=2048)


def test_toy_config_keeps_every_config1_key():
    assert set(TOY) == set(chip_smoke.CONFIG1)


def test_stages_at_toy_size():
    report = chip_smoke.run_all(TOY, jax.devices()[:8],
                                chip_smoke.CompileMeter(), interpret=True)
    stages = report["stages"]
    assert list(stages) == ["probes", "scanned", "eager", "serving",
                            "kernels", "dist"]
    assert report["ok"], {k: v.get("error") for k, v in stages.items()}
    assert stages["scanned"]["steps"] == 4
    assert stages["scanned"]["compiles_after_first_group"] == 0
    assert stages["dist"]["mesh_devices"] == 8
    # Interpret mode refuses nothing and every point matches its XLA arm.
    kernels = stages["kernels"]["kernels"]
    assert {k.split("/")[0] for k in kernels} >= {
        "gather_f32", "gather_dq_bf16", "gather_dq_int8", "fused_f32",
        "fused_dq_bf16", "fused_dq_int8", "sample"}
    for name, row in kernels.items():
        assert row["ok"] and not row["refused"] and not row["mismatch"], (
            name, row)


def test_a_failing_stage_fails_the_run():
    report = {"stages": {}}

    def boom():
        raise chip_smoke.SmokeFailure("losses were not finite")

    ok = chip_smoke.run_stage(report, chip_smoke.CompileMeter(), "boom",
                              boom)
    assert ok is False
    assert report["stages"]["boom"]["ok"] is False
    assert "losses were not finite" in report["stages"]["boom"]["error"]


def test_last_stdout_line_is_the_verdict_and_nothing_else(monkeypatch,
                                                           capsys):
    """The driver parses the last stdout line: exactly ``ok`` and
    ``device``, the device exactly platform / kind / count.  The report
    goes on the line before it."""
    import json

    facts = chip_smoke.device_facts()
    facts["device"]["platform"] = "tpu"            # get past the refusal
    monkeypatch.setattr(chip_smoke, "device_facts", lambda: facts)
    monkeypatch.setattr(
        chip_smoke, "run_all",
        lambda *a, **kw: {"ok": True, "stages": {"probes": {"ok": True}}})
    monkeypatch.setattr("glt_tpu.obs.roofline.peak_bf16_tflops",
                        lambda kind: 197.0)
    monkeypatch.setattr("glt_tpu.utils.enable_compile_cache",
                        lambda: "/nowhere")
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    report = json.loads(lines[0])["report"]
    assert report["ok"] and report["compile_cache_dir"] == "/nowhere"
    assert report["stages"] == {"probes": {"ok": True}}
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert isinstance(last["device"]["platform"], str)
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int


def test_script_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no result line
    assert "found platform 'cpu'" in proc.stderr


def _force_tpu_sweep(monkeypatch):
    """Make the autotuners believe they are on a TPU: the XLA arm runs
    on the CPU and every compiled-Pallas candidate raises (the CPU
    backend only interprets), which is the refusal to record."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_refused_gather_candidate_is_recorded(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import gather_pallas as gp

    _force_tpu_sweep(monkeypatch)
    gp.reset_autotune()
    try:
        table = jnp.asarray(np.random.default_rng(0).normal(
            size=(64, 128)).astype(np.float32))
        idx = jnp.arange(300, dtype=jnp.int32) % 64
        assert gp.autotune_gather_rows(table, idx) == "xla"
        entry = gp.autotune_table()["d128_b300_float32"]
        assert entry["winner"] == "xla" and "xla" in entry["ms"]
        assert set(entry["refused"]) == {
            gp._fmt_params(p)
            for p in gp.candidate_gather_params(128, jnp.float32)}
        assert all(": " in msg for msg in entry["refused"].values())
    finally:
        gp.reset_autotune()


def test_refused_sample_sweep_is_recorded(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import sample_pallas as sp

    _force_tpu_sweep(monkeypatch)
    rng = np.random.default_rng(0)
    indptr = jnp.asarray(np.arange(0, 4097 * 4, 4)[:4097].astype(np.int32))
    indices = jnp.asarray(rng.integers(0, 4096, 4096 * 4).astype(np.int32))
    seeds = jnp.arange(64, dtype=jnp.int32)
    sp.reset_autotune()
    try:
        # The standing refusal: no sweep, the reason in the table.
        assert sp.autotune_sample(indptr, indices, seeds, 3) == "xla"
        entry = sp.sample_autotune_table()["b64_f3_int32"]
        assert entry["ms"] == {}
        assert entry["refused"] == {"all": sp.TPU_REFUSAL}
        # With it lifted the sweep runs and names each refused candidate.
        sp.reset_autotune()
        monkeypatch.setattr(sp, "TPU_REFUSAL", None)
        assert sp.autotune_sample(indptr, indices, seeds, 3) == "xla"
        entry = sp.sample_autotune_table()["b64_f3_int32"]
        assert "xla" in entry["ms"]
        assert set(entry["refused"]) == {
            sp._fmt_params(p) for p in sp.candidate_sample_params()}
    finally:
        sp.reset_autotune()
