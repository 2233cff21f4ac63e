"""The chip smoke's serve-and-compare path, on the CPU at reduced width.

``chip_smoke.py`` serves qwen3-4b at full width on a TPU; here its
``serve_and_check`` runs the same fleet path and model-level reference on
``reduced_config("qwen3-4b")`` (the platform is the test's: CPU), one
replica per pool layout, and four device-bound replicas on a forced
four-device host. Also the device-kind table the smoke prices with, and the
smoke's refusal to run without a TPU.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import reduced_config
from repro.core.energy import EnergyModel
from repro.hw import TPU_V5E, chip_for_device_kind
from repro.models import init_params_jit

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

ARCH = chip_smoke.ARCH
SEQ = 128


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(ARCH)
    params = init_params_jit(cfg, jax.random.PRNGKey(0))
    return cfg, params, chip_smoke.Reference(cfg, params, SEQ)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_serve_and_check_reduced(setup, paged):
    cfg, params, ref = setup
    trace = chip_smoke.make_trace(cfg, seed=0, prompt_len=(8, 48), max_new=12)
    res = chip_smoke.serve_and_check(
        ARCH, params, EnergyModel(TPU_V5E), trace, ref, reduced=True,
        paged=paged, max_seq_len=SEQ, log=lambda *_: None)
    assert len(res["checks"]) == len(trace)
    assert all(c["ok"] and c["tokens"] == 12 for c in res["checks"])
    assert res["stats"].decode_steps > 0


def test_reference_rejects_tokens_off_the_argmax(setup):
    """The check has teeth: a served stream that is not the model's fails."""
    cfg, params, ref = setup
    trace = chip_smoke.make_trace(cfg, seed=1, n=1, prompt_len=(16, 16), max_new=6)
    bad = ref.check(trace[0].prompt, [0] * 6)
    assert not bad["ok"] and bad["max_margin"] > chip_smoke.LOGIT_TOL


def test_four_bound_replicas_decode_under_shard_map():
    """Four replicas bound one per device on a forced four-device host:
    every fused decode step runs under shard_map over their devices (no
    vmap fallback, no serial step), and every token passes the model-level
    reference. Subprocess: the device count is fixed per process."""
    code = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec\n"
        "import chip_smoke as cs\n"
        "from repro.configs import reduced_config\n"
        "from repro.core.energy import EnergyModel\n"
        "from repro.hw import TPU_V5E\n"
        "from repro.models import init_params_jit\n"
        "from repro.serving.pool import params_on_device\n"
        "devs = jax.devices()\n"
        "assert len(devs) == 4, devs\n"
        "cfg = reduced_config(cs.ARCH)\n"
        "params = init_params_jit(cfg, jax.random.PRNGKey(0),\n"
        "    NamedSharding(Mesh(devs, ('replica',)), PartitionSpec()))\n"
        "trace = cs.make_trace(cfg, seed=0, aligned=True,\n"
        "    prompt_len=(cs.ALIGNED_PROMPT_LEN, cs.ALIGNED_PROMPT_LEN), max_new=8)\n"
        "ref = cs.Reference(cfg, params_on_device(params, devs[0]), 512)\n"
        "res = cs.serve_and_check(cs.ARCH, params, EnergyModel(TPU_V5E), trace,\n"
        "    ref, reduced=True, paged=False, max_seq_len=512, replicas=4,\n"
        "    devices=devs, engine_opts={'batch_layout': 'shard_map'},\n"
        "    log=lambda *_: None)\n"
        "st = res['stats']\n"
        "assert st.shard_map_calls == st.fused_decode_calls > 0, st\n"
        "assert st.vmap_fallbacks == 0 and st.serial_decode_calls == 0, st\n"
        "print('OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_device_kind_table():
    assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no HardwareSpec for device kind"):
        chip_for_device_kind("TPU v99")
