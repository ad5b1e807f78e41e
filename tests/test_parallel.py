"""Distribution substrate: sharding rules + multi-device collectives
(subprocess with 8 forced host devices; smoke tests here see 1 device)."""

import jax
import numpy as np
import pytest

from repro.parallel.sharding import RULES, logical_to_spec


class _FakeMesh:
    def __init__(self, axis_names):
        self.axis_names = axis_names


def test_logical_to_spec_drops_missing_axes():
    mesh = _FakeMesh(("data", "model"))
    spec = logical_to_spec(("batch", None, "heads"), mesh)
    assert spec[0] == "data"  # pod dropped (absent), data kept
    assert spec[1] is None
    assert spec[2] == "model"


def test_logical_to_spec_no_double_axis_use():
    mesh = _FakeMesh(("data", "model"))
    # batch uses data; a second data-mapped name in the same spec must drop
    spec = logical_to_spec(("batch", "embed"), mesh)
    assert spec[0] == "data" and spec[1] is None


def test_logical_to_spec_multi_axis():
    mesh = _FakeMesh(("pod", "data", "model"))
    spec = logical_to_spec(("batch",), mesh)
    assert spec[0] == ("pod", "data")


def test_rules_cover_model_axes():
    for name in ("batch", "heads", "mlp", "experts", "vocab", "table", "edges"):
        assert name in RULES


def test_ring_matmul_and_sp_decode(multidevice):
    out = multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel import collectives
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
ring = jax.shard_map(lambda xs, ws: collectives.ring_matmul(xs, ws, "data"),
                     mesh=mesh, in_specs=(P("data", None), P(None, "data")),
                     out_specs=P("data", None), check_vma=False)
np.testing.assert_allclose(np.asarray(ring(x, w)), np.asarray(x @ w), rtol=1e-5, atol=1e-5)

B, H, G, Dh, S = 2, 8, 4, 16, 64
q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
fn = collectives.make_sp_decode(mesh, "data")
got = fn(q, k, v, 0.25)
qg = np.asarray(q).reshape(B, G, H//G, Dh)
s = np.einsum("bgrd,bsgd->bgrs", qg, np.asarray(k)) * 0.25
p = np.exp(s - s.max(-1, keepdims=True)); p /= p.sum(-1, keepdims=True)
want = np.einsum("bgrs,bsgd->bgrd", p, np.asarray(v)).reshape(B, H, Dh)
np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
print("COLLECTIVES_OK")
""",
        8,
    )
    assert "COLLECTIVES_OK" in out


def test_pipeline_parallel(multidevice):
    out = multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.parallel import pipeline
mesh = jax.make_mesh((4,), ("pod",))
stage_params = [{"w": jnp.eye(8) * (i + 1)} for i in range(4)]
x = jnp.asarray(np.random.default_rng(3).normal(size=(6, 4, 8)), jnp.float32)
y = pipeline.pipeline_apply(lambda p, h: h @ p["w"], stage_params, x, mesh, axis="pod")
np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 24.0, rtol=1e-5)
print("PIPELINE_OK")
""",
        4,
    )
    assert "PIPELINE_OK" in out


def test_grad_compression_and_compressed_psum(multidevice):
    # single-device error-feedback invariants
    import jax.numpy as jnp

    from repro.optim import grad_compress as gc

    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32)}
    err = gc.init_error(g)
    codes, scales, err2 = gc.compress_grads(g, err)
    recon = jax.tree.map(gc.dequantize_leaf, codes, scales)
    # error feedback: residual = corrected - recon
    np.testing.assert_allclose(
        np.asarray(g["w"]) - np.asarray(recon["w"]), np.asarray(err2["w"]), rtol=1e-5, atol=1e-6
    )
    assert codes["w"].dtype == jnp.int8

    out = multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim import grad_compress as gc
mesh = jax.make_mesh((8,), ("data",))
sync = gc.make_compressed_psum(("data",))
g = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32)), jnp.float32)
def f(gs, es):
    out, e2 = sync({"g": gs}, {"g": es})
    return out["g"], e2["g"]
fn = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P(), P("data")), check_vma=False)
synced, err = fn(g, jnp.zeros_like(g))
want = np.asarray(g).mean(0)  # mean over shards (each shard = one row)
got = np.asarray(synced)[0]
rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
assert rel < 0.02, rel  # int8 quantization error bound
print("COMPRESS_OK", rel)
""",
        8,
    )
    assert "COMPRESS_OK" in out


def test_moe_apply_multidevice_matches_dense(multidevice):
    """EP MoE (experts sharded over 'model') == single-device reference."""
    out = multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.models import moe as moe_lib
cfg = moe_lib.MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, n_shared=0,
                        first_dense=0, capacity_factor=8.0)  # no drops
params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, 32, jnp.float32)
x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 32)), jnp.float32)

mesh1 = jax.make_mesh((1, 1), ("data", "model"))
y1, aux1 = moe_lib.moe_apply(params, x, cfg, mesh1, ("data",))
mesh8 = jax.make_mesh((2, 4), ("data", "model"))
y8, aux8 = moe_lib.moe_apply(params, x, cfg, mesh8, ("data",))
np.testing.assert_allclose(np.asarray(y1), np.asarray(y8), rtol=2e-4, atol=2e-5)
print("MOE_EP_OK")
""",
        8,
    )
    assert "MOE_EP_OK" in out
