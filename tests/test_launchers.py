"""End-to-end smoke of the production launchers (train / serve) on the
single host device with reduced configs — the same entry points a real
deployment calls with the full configs."""
import json
import os

import jax
import pytest

from repro.launch import cache
from repro.launch import serve as serve_launch
from repro.launch import train as train_launch


def test_train_launcher_monolithic(tmp_path):
    hist = train_launch.main([
        "--arch", "xlstm-125m", "--reduced", "--steps", "4",
        "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path)])
    assert "phase1" in hist
    losses = [h["loss"] for h in hist["phase1"]]
    assert all(l == l for l in losses)            # no NaNs
    assert os.path.exists(tmp_path / "xlstm-125m.npz")


def test_train_launcher_cascade_dpi(tmp_path):
    hist = train_launch.main([
        "--arch", "qwen2.5-3b", "--reduced", "--steps", "6",
        "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
        "--cascade"])
    ens = hist["cascade"]
    assert len(ens["losses"]) >= 2
    # Algorithm 1's Ensure line: later modes at most as good
    assert ens["losses"][0] <= ens["losses"][1] + 0.5   # smoke-scale slack


def test_serve_launcher_policies(tmp_path):
    dyn = serve_launch.main([
        "--arch", "qwen2.5-3b", "--reduced", "--requests", "2",
        "--prompt-len", "4", "--gen", "6", "--cache-len", "32",
        "--json-out", str(tmp_path / "dyn.json")])
    assert dyn["tokens"] == 12
    assert dyn["wire_bytes_per_token"] >= 0
    st1 = serve_launch.main([
        "--arch", "qwen2.5-3b", "--reduced", "--requests", "2",
        "--prompt-len", "4", "--gen", "6", "--cache-len", "32",
        "--policy", "static1"])
    st0 = serve_launch.main([
        "--arch", "qwen2.5-3b", "--reduced", "--requests", "2",
        "--prompt-len", "4", "--gen", "6", "--cache-len", "32",
        "--policy", "static0"])
    # the bottleneck mode must be strictly cheaper on the wire than raw
    assert st1["wire_bytes"] < st0["wire_bytes"]
    assert json.load(open(tmp_path / "dyn.json"))["policy"] == "orchestrator"


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins
    and is left to JAX; without it, one fixed gitignored path inside the
    checkout."""
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cache.ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(cache.ENV)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        ignored = open(os.path.join(repo, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
