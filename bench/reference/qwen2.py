"""Plain reference of a Qwen2 decoder (arXiv:2407.10671), the yardstick
for the served model's tokens.  Imports nothing of the program.

One causal forward over a whole sequence in float32 with every matrix
product at ``HIGHEST`` precision, layer by layer (``lax.scan``), each
layer's bf16 weights widened as it is reached, so that the float32
copy of the model never exists whole.  Published equations:

* RMSNorm ``x / sqrt(mean(x^2) + eps) * g``;
* q, k, v projections with bias, RoPE on q and k (rotate-half pairs,
  ``theta ** (-2i / head_dim)``), grouped-query causal softmax attention
  at ``1 / sqrt(head_dim)``, output projection without bias;
* gated MLP ``(silu(x Wg) * (x Wu)) Wd``;
* residual adds, a final RMSNorm, and the head tied to the embedding.

Weights are a dict (``embed.table``, ``final_norm.scale`` and stacked
``layers.*``, see :func:`shapes`) made by the benchmark from the seed.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (a scale per row of the activations and per
output column of the weights), the precision below the bfloat16 that
the configuration serves in.

:func:`check_program` holds the program's config to the configuration's
file: the benchmark serves only a program that is this architecture.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
ROWS = 512          # logits are formed this many positions at a time
PAD = 1024          # sequences are padded to a multiple of this: few shapes

# what the program's config must say for the configuration's file to be
# what it serves: program field -> file key
PROGRAM_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                  "n_heads": "num_attention_heads",
                  "n_kv_heads": "num_key_value_heads",
                  "d_ff": "intermediate_size", "vocab_size": "vocab_size",
                  "head_dim": "head_dim", "rope_theta": "rope_theta",
                  "tie_embeddings": "tie_word_embeddings"}


def check_program(cfg: dict, program_cfg) -> None:
    """Refuses a program config whose sizes differ from the file's, or
    that lacks the q/k/v bias, RMSNorm or SiLU of a Qwen2 layer."""
    pc = program_cfg
    bad = {f: (getattr(pc, f), cfg[k]) for f, k in PROGRAM_FIELDS.items()
           if k in cfg and getattr(pc, f) != cfg[k]}
    if bad or not pc.qkv_bias or pc.norm != "rmsnorm" or pc.act != "silu":
        raise ValueError(f"program config {cfg['program_config']!r} is "
                         f"not the configuration's file: {bad}")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "hkv": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "theta": cfg["rope_theta"]}


def shapes(cfg: dict, padded_vocab: int) -> dict:
    """The weight tree this reference reads, by shape."""
    k = dims(cfg)
    d, q, kv, f, L = k["d"], k["h"] * k["hd"], k["hkv"] * k["hd"], \
        k["f"], k["L"]
    return {"embed": {"table": (padded_vocab, d)},
            "final_norm": {"scale": (d,)},
            "layers": {"ln1": {"scale": (L, d)},
                       "attn": {"wq": (L, d, q), "wk": (L, d, kv),
                                "wv": (L, d, kv), "wo": (L, q, d),
                                "bq": (L, q), "bk": (L, kv),
                                "bv": (L, kv)},
                       "ln2": {"scale": (L, d)},
                       "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                               "w_down": (L, f, d)}}}


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _f8(a, -1), _f8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    # x: (S, H, hd)
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v):
    """Causal softmax attention, ``ROWS`` queries at a time so that the
    scores of a long sequence never exist whole.  q, k, v: (S, H, hd)."""
    S, H, hd = q.shape
    pos = jnp.arange(S)

    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        mask = (start + jnp.arange(ROWS))[None, :, None] >= pos[None, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (q.reshape(S // ROWS, ROWS, H, hd),
                              jnp.arange(S // ROWS) * ROWS))
    return out.reshape(S, H * hd)


def _layer(k, quant, x, lw):
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
    S = x.shape[0]
    pos = jnp.arange(S)
    a = lw["attn"]
    h = _rms(x, lw["ln1"]["scale"], k["eps"])
    q = (_mm(h, a["wq"], quant) + a["bq"]).reshape(S, k["h"], k["hd"])
    kk = (_mm(h, a["wk"], quant) + a["bk"]).reshape(S, k["hkv"], k["hd"])
    v = (_mm(h, a["wv"], quant) + a["bv"]).reshape(S, k["hkv"], k["hd"])
    q, kk = _rope(q, pos, k["theta"]), _rope(kk, pos, k["theta"])
    rep = k["h"] // k["hkv"]
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    x = x + _mm(_attention(q, kk, v), a["wo"], quant)
    m = lw["mlp"]
    h = _rms(x, lw["ln2"]["scale"], k["eps"])
    x = x + _mm(jax.nn.silu(_mm(h, m["w_gate"], quant))
                * _mm(h, m["w_up"], quant), m["w_down"], quant)
    return x, None


def hidden(w: dict, tokens: jax.Array, cfg: dict, quant=None) -> jax.Array:
    """Final-norm hidden states (S, d) of one sequence."""
    k = dims(cfg)
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, k, quant), x, w["layers"])
    return _rms(x, w["final_norm"]["scale"].astype(jnp.float32), k["eps"])


def _logits(w, x, cfg, quant=None):
    table = w["embed"]["table"][:cfg["vocab_size"]].astype(jnp.float32)
    return _mm(x, table.T, quant)


def _rows(x, ROWS_):
    S = x.shape[0]
    return x.reshape(S // ROWS_, ROWS_, *x.shape[1:])


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def served_gaps(w, tokens, served, cfg_items):
    """For each position of ``tokens`` (length a multiple of
    :data:`ROWS`): the reference's best logit minus its logit of the
    token ``served`` there.  Where the served token is the reference's
    own greedy choice the gap is 0."""
    cfg = dict(cfg_items)
    x = hidden(w, tokens, cfg)

    def one(_, xs):
        xb, sb = xs
        lg = _logits(w, xb, cfg)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, sb[:, None], -1)[:, 0]
        return None, best - got

    _, g = jax.lax.scan(one, None, (_rows(x, ROWS), _rows(served, ROWS)))
    return g.reshape(-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def control_gaps(w, tokens, cfg_items, quant="fp8"):
    """For each position of ``tokens``: the reference's best logit minus
    its logit of the token that the reference computed at ``quant``
    puts first there."""
    cfg = dict(cfg_items)
    x = hidden(w, tokens, cfg)
    xc = hidden(w, tokens, cfg, quant)

    def one(_, xs):
        xb, xcb = xs
        lg = _logits(w, xb, cfg)
        pick = jnp.argmax(_logits(w, xcb, cfg, quant), -1)
        got = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return None, jnp.max(lg, -1) - got

    _, g = jax.lax.scan(one, None, (_rows(x, ROWS), _rows(xc, ROWS)))
    return g.reshape(-1)


def cfg_items(cfg: dict) -> tuple:
    """The configuration as a hashable static argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta", "head_dim")
    return tuple((k, cfg[k]) for k in keys if k in cfg)
