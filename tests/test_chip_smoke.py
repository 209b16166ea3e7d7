"""``chip_smoke.py`` on the CPU: its phases at tiny width (interpret-mode
kernels), its refusal to run without a TPU, and the compile-cache path
every entry point uses."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def _tiny_sizes(cs):
    return cs.Sizes(n_docs=256, n_queries=64, n_requests=8,
                    encode_batch=32, superchunk=4, nclusters=8, nprobe=2)


def test_one_chip_phases_at_tiny_width(tiny_lm_cfg, tmp_path, capsys):
    cs = _load_chip_smoke()
    results = cs.run_one_chip(tiny_lm_cfg, str(tmp_path), _tiny_sizes(cs))
    assert set(results) == {"b_flat_jax", "c_flat_pallas_fused",
                            "d_flat_pallas_heap", "e_ivf_full_probe",
                            "e_ivf_nprobe"}
    for name, res in results.items():
        assert res["ids"].shape == (8 * 8, 10), name
        if not name.endswith("nprobe"):
            assert res["overlap"] == 1.0, name
    out = capsys.readouterr().out
    assert "phase a_encode" in out
    assert '"equal": true' in out


def test_sharded_phase_puts_each_worker_on_its_device(tmp_path):
    """Four workers on four virtual CPU devices equal one worker, and
    worker r's corpus and params sit on device r (the rehearsal of
    ``--chips 4``)."""
    prog = textwrap.dedent(f"""
        import jax.numpy as jnp
        from repro.models.transformer import LMConfig
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        cfg = LMConfig(name="tiny", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=257,
                       dtype=jnp.float32, pooling="mean", remat=False)
        sizes = cs.Sizes(n_docs=256, n_queries=64, n_requests=8,
                         encode_batch=32, superchunk=4)
        out = cs.run_sharded(cfg, {str(tmp_path)!r}, 4, sizes)
        devs = [w["device"] for w in out["many"]["during"]["workers"]]
        assert len(set(devs)) == 4, devs
        print("SHARDED_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "SHARDED_OK" in res.stdout
    assert '"equal": true' in res.stdout


def test_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run([sys.executable, SCRIPT], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    # refused before any work: no config, phase or result line
    assert res.stdout == ""


@pytest.mark.parametrize("env_dir", [None, "/some/cache/dir"])
def test_compile_cache_dir(monkeypatch, env_dir):
    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = env_dir
    assert compile_cache.compile_cache_dir() == want


def test_compile_cache_dir_is_ignored_by_git():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
