"""Plain reference of the Soft-MoE ViT, written from the configuration file.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no scan, no mixed precision. It imports nothing of the program.
It reads parameters in the program's tree layout (``param_shapes``), which
the harness checks against the program's own abstract init before a run.

Model (what the repo's ViT computes; departures from the paper noted):
patch projection with bias plus learned position embedding; per block a
pre-LayerNorm (eps 1e-6) multi-head attention with RoPE on q and k
(theta from the file; the paper's ViT has no RoPE), no qkv bias, and a
pre-LayerNorm MLP without biases (the paper's has biases), GELU in its
tanh form; in the MoE blocks the MLP is replaced by Soft MoE (paper
Algorithm 1 with the Algorithm 2 L2 normalisation: logits =
l2n(X) @ (scale * l2n(Phi)), D softmax over tokens, C softmax over slots);
final LayerNorm, mean pool, linear head; loss is mean NLL.

``control=True`` is the same model with every matmul operand rounded to
float8 e4m3 with a per-tensor scale: the lower precision a later change
could be tempted by. The comparison has to fail it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
L2_EPS = 1e-6
F8_MAX = 448.0  # largest finite float8_e4m3fn


def moe_layers(cfg: dict) -> list:
    n = cfg["num_hidden_layers"]
    spec = cfg["moe_layers"]
    if spec == "second_half":
        return list(range(n // 2, n))
    if spec == "all":
        return list(range(n))
    return [int(i) for i in spec.split(",") if i]


def segments(cfg: dict) -> list:
    """Contiguous runs ``(start, count, is_moe)`` of blocks alike."""
    moe = set(moe_layers(cfg))
    segs = []
    for i in range(cfg["num_hidden_layers"]):
        if segs and segs[-1][2] == (i in moe):
            segs[-1][1] += 1
        else:
            segs.append([i, 1, i in moe])
    return [tuple(s) for s in segs]


def param_shapes(cfg: dict) -> dict:
    """Shapes of every parameter, in the program's nested layout: blocks
    of one segment are stacked on a leading axis."""
    d, h, k = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    f, m, pd = cfg["intermediate_size"], cfg["num_patches"], cfg["patch_dim"]
    n, p = cfg["num_experts"], cfg["slots_per_expert"]
    norm = lambda *lead: {"scale": (*lead, d), "bias": (*lead, d)}  # noqa: E731
    segs = []
    for _, c, is_moe in segments(cfg):
        blk = {"norm1": norm(c), "norm2": norm(c),
               "attn": {"wq": (c, d, h, k), "wk": (c, d, h, k),
                        "wv": (c, d, h, k), "wo": (c, h, k, d)}}
        if is_moe:
            blk["moe"] = {"phi": (c, d, n, p), "scale": (c,),
                          "experts": {"w_up": (c, n, d, f),
                                      "w_down": (c, n, f, d)}}
        else:
            blk["mlp"] = {"w_up": (c, d, f), "w_down": (c, f, d)}
        segs.append(blk)
    return {"patch_proj": {"w": (pd, d), "b": (d,)}, "pos_emb": (m, d),
            "segments": segs, "final_norm": norm(),
            "head": {"w": (d, cfg["num_classes"]),
                     "b": (cfg["num_classes"],)}}


def _quant(x):
    """Round to float8 e4m3 under a per-tensor scale."""
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _f8_einsum(spec: str):
    """An einsum whose operands are rounded to float8 in the forward pass
    and whose backward products round theirs too, as fp8 training does."""
    ins, out = spec.split("->")
    a_s, b_s = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _quant(a), _quant(b), precision="highest")

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        qa, qb, qg = _quant(a), _quant(b), _quant(g)
        da = jnp.einsum(f"{out},{b_s}->{a_s}", qg, qb, precision="highest")
        db = jnp.einsum(f"{a_s},{out}->{b_s}", qa, qg, precision="highest")
        return da, db

    f.defvjp(fwd, bwd)
    return f


def _mm(control: bool):
    def mm(spec, a, b):
        if control:
            return _f8_einsum(spec)(a, b)
        return jnp.einsum(spec, a, b, precision="highest")
    return mm


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rope(x, theta: float):
    """x: (b, m, h, k); half-rotation RoPE at positions 0..m-1."""
    m, k = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = jnp.arange(m, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, x, cfg, mm):
    q = _rope(mm("bmd,dhk->bmhk", x, p["wq"]), cfg["rope_theta"])
    k = _rope(mm("bmd,dhk->bmhk", x, p["wk"]), cfg["rope_theta"])
    v = mm("bmd,dhk->bmhk", x, p["wv"])
    s = mm("bqhk,bthk->bhqt", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    o = mm("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return mm("bmhk,hkd->bmd", o, p["wo"])


def _l2n(x, axis):
    return x / (jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True)) + L2_EPS)


def _soft_moe(p, x, mm):
    b, m, d = x.shape
    _, n, s = p["phi"].shape
    phi = p["scale"] * _l2n(p["phi"].reshape(d, n * s), 0)
    logits = mm("bmd,ds->bms", _l2n(x, -1), phi)
    disp = jax.nn.softmax(logits, axis=1)   # over tokens, per slot
    comb = jax.nn.softmax(logits, axis=2)   # over slots, per token
    slots = mm("bms,bmd->bsd", disp, x).reshape(b, n, s, d)
    h = _gelu(mm("bnsd,ndf->bnsf", slots, p["experts"]["w_up"]))
    ys = mm("bnsf,nfd->bnsd", h, p["experts"]["w_down"]).reshape(b, n * s, d)
    return mm("bms,bsd->bmd", comb, ys)


def _mlp(p, x, mm):
    return mm("bmf,fd->bmd", _gelu(mm("bmd,df->bmf", x, p["w_up"])),
              p["w_down"])


def _layer(seg: dict, j: int) -> dict:
    return jax.tree.map(lambda a: a[j], seg)


def logits(params, patches, cfg: dict, control: bool = False):
    """(b, num_patches, patch_dim) -> (b, num_classes), f32."""
    mm = _mm(control)
    x = mm("bmp,pd->bmd", patches.astype(jnp.float32),
           params["patch_proj"]["w"])
    x = x + params["patch_proj"]["b"] + params["pos_emb"]
    for seg, (_, count, is_moe) in zip(params["segments"], segments(cfg)):
        for j in range(count):
            def block(x, p, _moe=is_moe):
                x = x + _attention(p["attn"], _ln(x, p["norm1"]), cfg, mm)
                h = _ln(x, p["norm2"])
                return x + (_soft_moe(p["moe"], h, mm) if _moe
                            else _mlp(p["mlp"], h, mm))
            # remat keeps the reference's activations to one block
            x = jax.checkpoint(block)(x, _layer(seg, j))
    pooled = _ln(x, params["final_norm"]).mean(axis=1)
    return mm("bd,dc->bc", pooled, params["head"]["w"]) + params["head"]["b"]


def loss(params, batch, cfg: dict, control: bool = False):
    lp = jax.nn.log_softmax(logits(params, batch["patches"], cfg, control))
    return -jnp.take_along_axis(lp, batch["labels"][:, None], -1).mean()
