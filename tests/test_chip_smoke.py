"""Rehearses ``chip_smoke.py`` on the CPU at the ``reduced`` preset.

The phases are the chip run's own functions: serving through
``submit``/``run`` with its checks, the step-executable inspection, and
every fused kernel against its oracle (Pallas interpret mode here). The
four-chip ``ShardPlan`` phase runs in a subprocess over four forced host
devices, so this process keeps seeing one. ``main()`` must refuse the CPU.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    return chip_smoke.preset("reduced")


@pytest.fixture(scope="module")
def engine(reduced):
    return chip_smoke.make_engine(chip_smoke.build_params(reduced, 0),
                                  reduced, 0)


def test_full_preset_keeps_published_widths():
    p = chip_smoke.preset("full")
    cfg = p.cfg
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab) == (5120, 40, 10, 128, 17920, 100352)
    assert cfg.n_layers == chip_smoke.LAYERS == 4
    assert cfg.kv_quant == "takum8"
    wave1, wave2 = chip_smoke.traffic(p, 0)
    lens = [len(x) for x in wave1 + wave2]
    assert len(lens) == 8 and min(lens) >= 128 and max(lens) <= 1024
    assert wave2[1] == wave1[-1][:p.prefix] == wave2[0][:p.prefix]
    # every prefill width is one of three compile buckets
    widths = {chip_smoke._prefill_width(p, n) for n in lens}
    assert widths == {320, 768, 1088}
    assert p.max_len >= max(lens) + p.max_new


def test_serve_phase(engine, reduced):
    out = chip_smoke.serve(engine, reduced, 0)
    assert len(out["tokens"]) == len(reduced.lens) + 3
    assert out["stats"].prefix_hit_tokens > 0


def test_executables_phase(engine, reduced):
    texts = chip_smoke.step_executables(engine, reduced)
    assert set(texts) == {"prefill_chunk", "decode_step"}
    chip_smoke.check_executables(engine, reduced, expect_kernels=False)


def test_kernel_parity_phase(reduced):
    chip_smoke.kernel_parity(reduced, 0)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no TPU found" in out.err
    assert '"ok"' not in out.out


def test_sharded_phase_on_four_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = ("import chip_smoke as c; p = c.preset('reduced'); "
            "c.sharded_parity(p, 0); print('SHARDED OK')")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "SHARDED OK" in out.stdout
    # on the CPU gather mode keeps its bit-exact contract
    assert "gather: greedy tokens bit-exact" in out.stdout
