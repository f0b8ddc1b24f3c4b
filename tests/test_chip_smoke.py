"""``chip_smoke.py`` off the chip.

The script itself refuses any platform but a TPU, so these tests drive
its phases directly at the tiny preset (Pallas kernels in interpret
mode): the one-chip phases in this process, the four-chip phase in a
child with four virtual CPU devices. What only the chip can show — that
the compiled steps hold ``tpu_custom_call`` — is checked here the other
way round: on the CPU the recorded steps run interpreted kernels.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_tiny
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke as smoke  # noqa: E402

TINY = smoke.Sizes(kb_chunks=8, chunk_len=(24, 48), requests=4,
                   k_chunks=3, max_new=4, pool_blocks=512, seq_bucket=64,
                   decode_bucket_b=4, ref_bucket=32, server_requests=2)


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def test_refuses_cpu_and_names_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_engine_and_reference_phases(world):
    cfg, params = world
    times = smoke.phase_engine(cfg, params, TINY, seed=0)
    assert len(times) == 2 and min(times) > 0
    reqs, logits = smoke.phase_reference(cfg, params, TINY, seed=0)
    assert len(reqs) == TINY.requests
    assert all(logits[r.rid].shape[0] == TINY.max_new for r in reqs)


def test_kernel_phase_runs_interpreted_kernels(world, monkeypatch):
    cfg, params = world
    dense = smoke.phase_reference(cfg, params, TINY, seed=0)
    seen = {}

    def interpreted(name, recorders):
        # on the CPU the same steps must run, with the kernels
        # interpreted: no TPU custom call can appear
        for step, rec in recorders.items():
            assert rec.last is not None, (name, step)
            assert "tpu_custom_call" not in rec.compiled_text()
            seen.setdefault(name, []).append(step)

    monkeypatch.setattr(smoke, "assert_kernels_compiled", interpreted)
    smoke.phase_kernels(cfg, params, TINY, seed=0, dense=dense)
    assert seen == {"kernel": ["prefill window", "decode step"],
                    "paged_kernel": ["prefill window",
                                     "paged decode step"]}


def test_server_phase(world):
    cfg, params = world
    smoke.phase_server(cfg, params, TINY, seed=0)


def test_sharded_phase_on_four_virtual_devices():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path[:0] = [".", "src"]
import jax
import chip_smoke as smoke
from repro.configs import get_tiny
from repro.models import model as M
# four heads of each kind divide the four-device mesh
cfg = get_tiny("llama3-8b").replace(num_heads=4, num_kv_heads=4)
params = M.init_params(cfg, jax.random.PRNGKey(0))
sizes = smoke.Sizes(kb_chunks=8, chunk_len=(24, 48), requests=4,
                    k_chunks=3, max_new=4, pool_blocks=512, seq_bucket=64,
                    decode_bucket_b=4, ref_bucket=32)
smoke.phase_sharded(cfg, params, sizes, 0, 4)
print("SHARDED_PHASE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_PHASE_OK" in r.stdout
    assert r.stdout.count("peak_bytes_in_use") == 4
