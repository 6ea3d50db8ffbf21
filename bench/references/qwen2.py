"""Plain reference of the dense Qwen2 family, and its weights.

Straightforward `jax.numpy` in float32 at the highest matmul precision:
no kernels, no cache, no batching. It reads the published keys of a
configuration file (`hidden_size`, `num_attention_heads`, ...) and imports
nothing of the program under test.

Weights come from the benchmark's seed, never from the program: `weights`
draws every tensor on the device in one jitted call, in the type it is
served in (bfloat16), laid out as the program takes its parameters: every
layer stacked on a leading axis. The reference reads the same arrays,
widened to float32.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ----------------------------------------------------------------------
# shapes and weights
def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return dict(D=D, H=H, K=c["num_key_value_heads"], h=D // H,
                L=c["num_hidden_layers"], V=c["vocab_size"],
                eps=c["rms_norm_eps"], theta=c["rope_theta"],
                tied=bool(c["tie_word_embeddings"]),
                F=c["intermediate_size"])


def layer_shapes(d: dict) -> dict:
    """name -> (shape, std, dtype) of one layer's tensors; std None means a
    norm scale (1 + 0.1 N(0, 1))."""
    D, H, K, h = d["D"], d["H"], d["K"], d["h"]
    return {"ln_attn": ((D,), None, "bfloat16"),
            "wq": ((D, H * h), 0.02, "bfloat16"),
            "wk": ((D, K * h), 0.02, "bfloat16"),
            "wv": ((D, K * h), 0.02, "bfloat16"),
            "wo": ((H * h, D), 0.02, "bfloat16"),
            "bq": ((H * h,), 0.1, "bfloat16"),
            "bk": ((K * h,), 0.1, "bfloat16"),
            "bv": ((K * h,), 0.1, "bfloat16"),
            "ln_mlp": ((D,), None, "bfloat16"),
            "w1": ((D, d["F"]), 0.02, "bfloat16"),
            "w3": ((D, d["F"]), 0.02, "bfloat16"),
            "w2": ((d["F"], D), 0.02, "bfloat16")}


def _draw(key, name: str, shape, std, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    x = 1.0 + 0.1 * z if std is None else std * z
    return x.astype(dtype)


def seed_key(seed: int):
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(seed) >> 32)


def weight_tree(d: dict, key) -> dict:
    """Every weight of the model from one key (traced: call under jit)."""
    L = d["L"]
    layer = {n: _draw(key, n, (L,) + shp, std, dt)
             for n, (shp, std, dt) in layer_shapes(d).items()}
    w = {"stack": {"period": (layer,), "rem": ()},
         "final_norm": _draw(key, "final_norm", (d["D"],), None, "bfloat16"),
         "embed": _draw(key, "embed", (d["V"], d["D"]), d["D"] ** -0.5,
                        "bfloat16")}
    if not d["tied"]:
        w["head"] = _draw(key, "head", (d["D"], d["V"]), 0.02, "bfloat16")
    return w


def weights(cfg: dict, seed: int, out_shardings=None) -> dict:
    """The served weights of `seed`, made on the device in one jitted call."""
    d = dims(cfg)
    fn = jax.jit(lambda k: weight_tree(d, k), out_shardings=out_shardings)
    return fn(seed_key(seed))


# ----------------------------------------------------------------------
# the forward pass
def _q8(x, axis):
    """x rounded to float8 e4m3 with a scale per slice along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """a [..., n] @ b [n, m]; in the control both operands are first
    rounded to float8 (a per row, b per output column)."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x [T, n, h], rotate-half convention."""
    h = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [T, h/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8: bool, block: int):
    """Causal GQA. q [T, H, h], k/v [T, K, h] → [T, H*h], queries in blocks
    of `block` rows."""
    T, H, h = q.shape
    K = k.shape[1]
    G = H // K
    kk = jnp.repeat(k, G, axis=1)                            # [T, H, h]
    vv = jnp.repeat(v, G, axis=1)
    if fp8:
        kk, vv = _q8(kk, -1), _q8(vv, 0)
    kpos = jnp.arange(T)

    def one(qb_i):
        qb, i = qb_i                                         # [B, H, h]
        if fp8:
            qb = _q8(qb, -1)
        s = jnp.einsum("bnh,tnh->nbt", qb, kk, precision=HIGHEST) * h ** -0.5
        qpos = i * block + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, None] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
        return jnp.einsum("nbt,tnh->bnh", p, vv, precision=HIGHEST)

    nb = T // block
    out = jax.lax.map(one, (q.reshape(nb, block, H, h), jnp.arange(nb)))
    return out.reshape(T, H * h)


def _layer(d, x, p, fp8: bool, block: int):
    T = x.shape[0]
    H, K, h = d["H"], d["K"], d["h"]
    pos = jnp.arange(T)
    a = _rms(x, p["ln_attn"], d["eps"])
    q = (_mm(a, p["wq"], fp8) + p["bq"]).reshape(T, H, h)
    k = (_mm(a, p["wk"], fp8) + p["bk"]).reshape(T, K, h)
    v = (_mm(a, p["wv"], fp8) + p["bv"]).reshape(T, K, h)
    q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    x = x + _mm(_attention(q, k, v, fp8, block), p["wo"], fp8)
    m = _rms(x, p["ln_mlp"], d["eps"])
    return x + _mm(jax.nn.silu(_mm(m, p["w1"], fp8)) * _mm(m, p["w3"], fp8),
                   p["w2"], fp8)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("cfg_key", "fp8", "block"))
def _score(w, tokens, targets, *, cfg_key, fp8: bool, block: int):
    d = dict(cfg_key)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, p):
        return _layer(d, x, _f32(p), fp8, block), None

    x, _ = jax.lax.scan(body, x, w["stack"]["period"][0])
    x = _rms(x, w["final_norm"].astype(jnp.float32), d["eps"])
    head = (w["embed"].T if d["tied"] else w["head"])

    def rows(args):
        xb, tb = args                                        # [B, D], [B]
        lg = _mm(xb, head.astype(jnp.float32), fp8)          # [B, V]
        top = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return top - got, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    T = x.shape[0]
    nb = T // block
    gap, arg = jax.lax.map(rows, (x.reshape(nb, block, -1),
                                  targets.reshape(nb, block)))
    return gap.reshape(T), arg.reshape(T)


def score(cfg: dict, w: dict, tokens, targets, fp8: bool = False,
          block: int = 256, bucket: int = 1024):
    """Run the reference over `tokens`. → (gap [T], argmax [T]) as numpy:
    at each position p, how far the logit of `targets[p]` lies below the
    best logit there, and which token is best. `fp8` computes every matmul
    with float8 operands: the control."""
    T = len(tokens)
    Tp = -(-T // bucket) * bucket
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    tgt = np.zeros(Tp, np.int32)
    tgt[:T] = targets
    key = tuple(sorted(dims(cfg).items()))
    gap, arg = _score(w, jnp.asarray(tok), jnp.asarray(tgt), cfg_key=key,
                      fp8=fp8, block=block)
    return np.asarray(gap)[:T], np.asarray(arg)[:T]


def logits(cfg: dict, w: dict, tokens) -> np.ndarray:
    """Full float32 logits [T, V] of a short sequence (for tests)."""
    d = dims(cfg)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    T = x.shape[0]
    for l in range(d["L"]):
        p = _f32(jax.tree.map(lambda a: a[l], w["stack"]["period"][0]))
        x = _layer(d, x, p, False, T)
    x = _rms(x, w["final_norm"].astype(jnp.float32), d["eps"])
    head = w["embed"].T if d["tied"] else w["head"]
    return np.asarray(_mm(x, head.astype(jnp.float32), False))
