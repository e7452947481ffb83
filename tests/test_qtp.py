"""Quantized tensor-parallel prefill (manual Megatron-SP schedule) vs the
monolithic forward. Subprocess with 8 forced host devices."""
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp
jax.config.update("jax_default_matmul_precision", "highest")
from repro.configs import get_reduced
from repro.core import split as S, qtp as QTP
from repro.launch.mesh import make_mesh
from repro.models import transformer as T

mesh = make_mesh((2, 4), ('data', 'model'))

for arch in ('stablelm-3b', 'granite-8b'):
    cfg = get_reduced(arch)
    if not QTP.qtp_supported(cfg, mesh, 32):
        continue
    params = S.init_split_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                             cfg.vocab_size)
    ref, _ = T.forward(params, tok, cfg)
    with jax.set_mesh(mesh):
        lg0 = jax.jit(lambda p, t: QTP.qtp_forward(
            p, t, cfg, mesh=mesh, bits=0))(params, tok)
        lg8 = jax.jit(lambda p, t: QTP.qtp_forward(
            p, t, cfg, mesh=mesh, bits=8))(params, tok)
    err0 = float(jnp.max(jnp.abs(lg0 - ref)))
    assert err0 < 0.1, f'{arch} bits=0 err {err0}'   # bf16 resid tolerance
    rel8 = float(jnp.linalg.norm((lg8 - ref).astype(jnp.float32))
                 / jnp.linalg.norm(ref.astype(jnp.float32)))
    assert rel8 < 0.05, f'{arch} bits=8 rel err {rel8}'
    # int8 must actually perturb (guards against bits being ignored)
    assert float(jnp.max(jnp.abs(lg8 - lg0))) > 1e-6
    print(arch, 'err0', err0, 'rel8', rel8)

# guard: unsupported shapes refuse the fast path
cfg = get_reduced('qwen2.5-3b')    # n_kv=2 on 4-wide model axis
assert not QTP.qtp_supported(cfg, mesh, 32)
cfg = get_reduced('mixtral-8x7b')  # MoE
assert not QTP.qtp_supported(cfg, mesh, 32)
print('QTP OK')
"""


def test_qtp_matches_monolithic_forward():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "QTP OK" in r.stdout
