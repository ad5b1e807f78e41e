"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + no NaNs — all 10 assigned architectures."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_archs, get

MESH = None


def mesh():
    global MESH
    if MESH is None:
        from repro.launch.mesh import make_mesh

        MESH = make_mesh((1, 1), ("data", "model"))
    return MESH


RNG = np.random.default_rng(0)

LM_ARCHS = ["qwen2.5-14b", "llama3-405b", "internlm2-20b", "deepseek-v2-lite-16b", "kimi-k2-1t-a32b"]
RECSYS_ARCHS = ["bst", "xdeepfm", "bert4rec", "autoint"]


def test_all_ten_archs_registered():
    names = set(all_archs())
    for n in LM_ARCHS + RECSYS_ARCHS + ["graphsage-reddit"]:
        assert n in names, n


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_train_and_decode(arch):
    spec = get(arch)
    b = spec.build(mesh(), shape_name="train_4k", smoke=True)
    model, cfg = b["model"], b["config"]
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.n_params(), f"{arch}: param count {n} != formula {cfg.n_params()}"
    info = b["shape_table"]["train_4k"]
    bs, s = info["global_batch"], info["seq_len"]
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab, (bs, s)).astype(np.int32))
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    opt = b["opt_init"](params)
    p2, o2, m = jax.jit(b["steps"]["train"])(params, opt, batch)
    loss = float(m["loss"])
    assert np.isfinite(loss) and loss > 0, (arch, loss)
    # shapes preserved by the update
    assert jax.tree.all(jax.tree.map(lambda a, c: a.shape == c.shape, p2, params))

    # one decode step against an empty cache
    db = spec.build(mesh(), shape_name="decode_32k", smoke=True)
    dinfo = db["shape_table"]["decode_32k"]
    cache = jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype),
        db["model"].cache_struct(dinfo["global_batch"], dinfo["seq_len"]),
    )
    tok = jnp.asarray(RNG.integers(0, cfg.vocab, (dinfo["global_batch"],)).astype(np.int32))
    logits, cache2 = jax.jit(db["steps"]["decode"])(params, cache, tok, jnp.asarray(0))
    assert logits.shape == (dinfo["global_batch"], cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("shape", ["full_graph_sm", "minibatch_lg", "ogb_products", "molecule"])
def test_gnn_smoke(shape):
    spec = get("graphsage-reddit")
    b = spec.build(mesh(), shape_name=shape, smoke=True)
    model, info = b["model"], b["shape_table"][shape]
    params = model.init(jax.random.PRNGKey(0))
    opt = b["opt_init"](params)
    kind = info["kind"]
    if kind == "train_full":
        n, e, f = info["n_nodes"], info["n_edges"], info["d_feat"]
        batch = {
            "feats": jnp.asarray(RNG.normal(size=(n, f)), jnp.float32),
            "edges": jnp.asarray(RNG.integers(0, n, (e, 2)).astype(np.int32)),
            "labels": jnp.asarray(RNG.integers(0, info["n_classes"], n).astype(np.int32)),
            "mask": jnp.ones((n,), jnp.float32),
        }
    elif kind == "train_mini":
        bs, (f1, f2), f = info["batch_nodes"], info["fanouts"], info["d_feat"]
        batch = {
            "x0": jnp.asarray(RNG.normal(size=(bs, f)), jnp.float32),
            "x1": jnp.asarray(RNG.normal(size=(bs, f1, f)), jnp.float32),
            "x2": jnp.asarray(RNG.normal(size=(bs, f1, f2, f)), jnp.float32),
            "labels": jnp.asarray(RNG.integers(0, info["n_classes"], bs).astype(np.int32)),
        }
    else:
        bs, n, e, f = info["batch"], info["n_nodes"], info["n_edges"], info["d_feat"]
        batch = {
            "feats": jnp.asarray(RNG.normal(size=(bs, n, f)), jnp.float32),
            "edges": jnp.asarray(RNG.integers(0, n, (bs, e, 2)).astype(np.int32)),
            "labels": jnp.asarray(RNG.integers(0, info["n_classes"], bs).astype(np.int32)),
        }
    p2, o2, m = jax.jit(b["steps"][kind])(params, opt, batch)
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("arch", RECSYS_ARCHS)
def test_recsys_smoke(arch):
    spec = get(arch)
    b = spec.build(mesh(), shape_name="train_batch", smoke=True)
    model, cfg = b["model"], b["config"]
    params = model.init(jax.random.PRNGKey(1))
    opt = b["opt_init"](params)
    bs = b["shape_table"]["train_batch"]["batch"]
    if cfg.kind in ("xdeepfm", "autoint"):
        batch = {
            "sparse": jnp.asarray(
                np.stack([RNG.integers(0, v, bs) for v in cfg.field_vocabs], 1).astype(np.int32)
            ),
            "label": jnp.asarray(RNG.integers(0, 2, bs).astype(np.float32)),
        }
    elif cfg.kind == "bst":
        batch = {
            "hist": jnp.asarray(RNG.integers(0, cfg.n_items, (bs, cfg.seq_len - 1)).astype(np.int32)),
            "hist_mask": jnp.ones((bs, cfg.seq_len - 1), bool),
            "target": jnp.asarray(RNG.integers(0, cfg.n_items, bs).astype(np.int32)),
            "label": jnp.asarray(RNG.integers(0, 2, bs).astype(np.float32)),
        }
    else:
        batch = {
            "seq": jnp.asarray(RNG.integers(0, cfg.n_items, (bs, cfg.seq_len)).astype(np.int32)),
            "mask": jnp.ones((bs, cfg.seq_len), bool),
            "mask_pos": jnp.asarray(RNG.integers(0, cfg.seq_len, (bs, cfg.n_mask)).astype(np.int32)),
            "mask_labels": jnp.asarray(RNG.integers(0, cfg.n_items, (bs, cfg.n_mask)).astype(np.int32)),
        }
    p2, o2, m = jax.jit(b["steps"]["train"])(params, opt, batch)
    assert np.isfinite(float(m["loss"]))

    # retrieval: dense tower and sketch tower both return valid top-k
    rb = spec.build(mesh(), shape_name="retrieval_cand", smoke=True)
    C, D = rb["shape_table"]["retrieval_cand"]["n_candidates"], cfg.embed_dim
    q = {
        "user_vec": jnp.asarray(RNG.normal(size=(1, D)), jnp.float32),
        "cand_emb": jnp.asarray(RNG.normal(size=(C, D)), jnp.float32),
    }
    sc, ids = jax.jit(rb["steps"]["retrieval"])(params, q)
    assert ids.shape[-1] == 100 and int(ids.max()) < C
    W = (rb["n_bins"] + 31) // 32
    qs = {
        "sketch": jnp.asarray(RNG.integers(0, 2**32, (1, W), dtype=np.uint64).astype(np.uint32)),
        "corpus_sketches": jnp.asarray(
            RNG.integers(0, 2**32, (C, W), dtype=np.uint64).astype(np.uint32)
        ),
    }
    sc2, ids2 = jax.jit(rb["steps"]["retrieval_sketch"])(params, qs)
    assert ids2.shape[-1] == 100 and int(ids2.max()) < C
