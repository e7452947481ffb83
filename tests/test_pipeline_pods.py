"""Pod-pipeline correctness (runs in a subprocess with 8 forced host devices
since the main test process must keep the single-device default)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import functools
import jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.core import split as S, pipeline as PL
from repro.launch.mesh import make_mesh
from repro.models import transformer as T

mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
cfg = get_reduced('stablelm-3b')
params = S.init_split_params(jax.random.PRNGKey(0), cfg)
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)

with jax.set_mesh(mesh):
    # mode 0: pipeline == monolithic forward (bf16 tolerance)
    fn0 = jax.jit(functools.partial(PL.pipeline_forward, cfg=cfg, mesh=mesh,
                                    n_micro=4, mode=0))
    lg0, _ = fn0(params, tok)
    ref0, _ = T.forward(params, tok, cfg)
    err0 = float(jnp.max(jnp.abs(lg0 - ref0)))
    assert err0 < 0.15, f'mode0 err {err0}'

    # mode 1: pipeline == split bottleneck forward
    fn1 = jax.jit(functools.partial(PL.pipeline_forward, cfg=cfg, mesh=mesh,
                                    n_micro=4, mode=1))
    lg1, _ = fn1(params, tok)
    ref1, _, _ = S.split_forward(params, tok, cfg, mode=1)
    err1 = float(jnp.max(jnp.abs(lg1 - ref1)))
    assert err1 < 0.25, f'mode1 err {err1}'

    # gradients flow through the quantized wire (STE) to BOTH stages and
    # to the bottleneck head
    def loss(params):
        lg, aux = PL.pipeline_forward(params, tok, cfg, mesh=mesh,
                                      n_micro=4, mode=1, train=True)
        return T.lm_loss(lg, tok) + 0.01 * aux
    g = jax.jit(jax.grad(loss))(params)
    def l1(t):
        return sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                   for x in jax.tree.leaves(t))
    assert l1(g['layers']) > 0
    assert l1(g['bneck_modes'][0]['down']) > 0
    assert l1(g['bneck_modes'][0]['up']) > 0

    # beyond-paper: int8 BACKWARD wire (pipeline2) — grads still flow and
    # stay close to the float-backward grads (quantized, not broken)
    def loss_q(params):
        lg, aux = PL.pipeline_forward(params, tok, cfg, mesh=mesh,
                                      n_micro=4, mode=1, train=True,
                                      bwd_bits=8)
        return T.lm_loss(lg, tok) + 0.01 * aux
    gq = jax.jit(jax.grad(loss_q))(params)
    assert l1(gq['layers']) > 0
    ref_n, q_n = l1(g['layers']), l1(gq['layers'])
    assert abs(ref_n - q_n) / max(ref_n, 1e-9) < 0.2, (ref_n, q_n)

    # int8 payload on the wire: the compiled HLO's collective-permute moves
    # s8 codes, and mode1 moves fewer bytes than mode0
    from repro.launch import roofline as R
    h0 = fn0.lower(params, tok).compile().as_text()
    h1 = fn1.lower(params, tok).compile().as_text()
    c0 = R.parse_collectives(h0)['collective-permute']
    c1 = R.parse_collectives(h1)['collective-permute']
    assert c1['bytes'] < 0.35 * c0['bytes'], (c0, c1)
    assert 's8[' in h1
print('PIPELINE_OK')
"""


@pytest.mark.slow
def test_pipeline_two_pods():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
