"""Plain reference of a pre-norm dense decoder with a MetaTT adapter.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one layer
at a time, with no kernel, cache or batching of the program under test. It
imports nothing of that program: the weights come from the benchmark's own
generator (``weights.py`` beside this file), made again from the seed layer
by layer, so that no more than one layer's weights are held at once.

The block (the repo's dense transformer path; a configuration file's
``runs_as`` and ``departures`` say where it differs from the published
model):

    h = x + Attn(Norm1(x)) ;  y = h + FFN(Norm2(h))
    Attn: q/k/v projections (+ adapter delta on adapted matrices), RoPE on
          every head dimension (half-split convention), causal softmax with
          grouped key/value heads, output projection.
    FFN:  down(silu(gate(x)) * up(x)).
    Readout: final norm, then the tied embedding.

The adapter on matrix m of layer l (task t for the 4+1d variant) adds
    alpha * x G1[:d_in] C[l, (t,) m] G_last[:, :d_out],
    C[l, (t,) m] = G2[:, l] (G3[:, t]) G_m[:, m],
with the TT cores of ``weights.cores``.

``quant`` selects the precision: ``None`` for the float32 reference;
``"fp8"`` (the control) rounds every matmul operand to float8 e4m3 with a
per-row (activations) or per-column (weights) scale, and every gradient
that flows back through one to e5m2 with a per-tensor scale, the precision
below the bfloat16 that the configurations state; ``"bf16"`` rounds them to
bfloat16 (a measure of what bf16 rounding alone does at full depth).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.lib.weights import seed_key

from . import weights as W

HI = jax.lax.Precision.HIGHEST
#: the block above, in the configuration files' keys
RUNS_AS = {"partial_rotary_factor": 1.0, "use_qkv_bias": False,
           "qk_layernorm": False, "use_parallel_residual": False,
           "tie_word_embeddings": True}
E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _round(a, axis, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                    1e-30) / top
    return (a / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(a, axis):
    """The usual fp8 training recipe: e4m3 values scaled by their amax
    along ``axis`` going forward; e5m2 gradients scaled by the tensor's
    amax coming back."""
    return _round(a, axis, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(a, axis):
    return _fp8(a, axis), None


def _fp8_bwd(axis, _, g):
    return (_round(g, None, jnp.float8_e5m2, E5M2_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def mm(a, b, quant=None):
    """a (..., K) @ b (K, N) in float32; the control rounds both operands."""
    return jnp.matmul(_q(a, quant), _q(b, quant, 0), precision=HI)


def norm(x, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def norm_of(cfg: dict):
    if "layer_norm_eps" in cfg:
        return functools.partial(norm, kind="layernorm",
                                 eps=cfg["layer_norm_eps"])
    return functools.partial(norm, kind="rmsnorm", eps=cfg["rms_norm_eps"])


def rope(x, pos, theta: float):
    """x (B, T, H, hd), pos (B, T): rotate_half RoPE over all of hd."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def tt_factors(cores, adapter: dict, l, task):
    """(A, B) with delta-y = alpha * (x A[:d_in]) B[:, :d_out] for every
    adapted matrix of layer ``l``: A (M, [Bt,] D_in, r), B (r, D_out)."""
    g1 = cores[0][0]                                   # (D_in, r)
    g2 = jax.lax.dynamic_index_in_dim(cores[1], l, 1, keepdims=False)
    if adapter["variant"] == "4d":
        gm = cores[2]                                  # (r, M, r)
        c = jnp.einsum("ab,bmc->mac", g2, gm, precision=HI)
        a = jnp.einsum("da,mac->mdc", g1, c, precision=HI)
    else:                                              # 4+1d, per-row task
        gt = cores[2][:, task]                         # (r, Bt, r)
        gm = cores[3]
        c = jnp.einsum("ab,bnc,cmd->mnad", g2, gt, gm, precision=HI)
        a = jnp.einsum("xa,mnad->mnxd", g1, c, precision=HI)
    return a, cores[-1][..., 0]


def _adapted(x, w, name, fac, adapter, quant):
    y = mm(x, w, quant)
    mats = adapter["matrices"]
    m_name = {v: k for k, v in W.MATRIX_LEAF.items()}[name]
    if m_name not in mats:
        return y
    mi = mats.index(m_name)
    a, b = fac
    a = a[mi][..., :x.shape[-1], :]
    b = b[:, :w.shape[1]]
    if a.ndim == 3:                                    # per-row task
        p = jnp.einsum("btk,bkr->btr", _q(x, quant), _q(a, quant, 1),
                       precision=HI)
    else:
        p = mm(x, a, quant)
    return y + adapter["alpha"] * mm(p, b, quant)


def _q(a, quant, axis=-1):
    if quant == "fp8":
        return _fp8(a, axis)
    if quant == "bf16":
        return _bf16(a)
    return a


def attend(q, k, v, q_pos, quant=None, block: int = 512):
    """Causal GQA attention. q (B, T, H, hd); k, v (B, S, KV, hd); q_pos
    (T,) absolute positions of the queries; key j sits at position j.
    Query blocks run one at a time under ``checkpoint`` so the (T, S)
    scores are never whole in memory, nor kept for the backward."""
    b, t, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    s_len = k.shape[1]

    @jax.checkpoint
    def one(qb, pb):
        qb = qb.reshape(b, -1, kvh, g, hd)
        s = jnp.einsum("btkgh,bskh->bkgts", _q(qb, quant), _q(k, quant),
                       precision=HI) * hd ** -0.5
        mask = pb[:, None] >= jnp.arange(s_len)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgts,bskh->btkgh", _q(p, quant), _q(v, quant, 1),
                       precision=HI)
        return o.reshape(b, -1, h, hd)

    nblk = max(1, -(-t // block))
    if nblk == 1:
        return one(q, q_pos)
    pad = nblk * block - t
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(q_pos, (0, pad))
    qs = qp.reshape(b, nblk, block, h, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: one(*a), (qs, pp.reshape(nblk, block)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, -1, h, hd)[:, :t]


def layer_fn(h, lw, cores, l, task, cfg: dict, adapter: dict, quant=None):
    """One block: (B, T, d) float32 -> (B, T, d)."""
    m = W.dims(cfg)
    nrm = norm_of(cfg)
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    fac = tt_factors(cores, adapter, l, task)
    ad = functools.partial(_adapted, fac=fac, adapter=adapter, quant=quant)
    b, t, _ = h.shape
    pos = jnp.arange(t)
    x = nrm(h)
    q = ad(x, lw["wq"], "wq").reshape(b, t, m["h"], m["hd"])
    k = ad(x, lw["wk"], "wk").reshape(b, t, m["kv"], m["hd"])
    v = ad(x, lw["wv"], "wv").reshape(b, t, m["kv"], m["hd"])
    theta = float(cfg["rope_theta"])
    pb = jnp.broadcast_to(pos, (b, t))
    q, k = rope(q, pb, theta), rope(k, pb, theta)
    o = attend(q, k, v, pos, quant).reshape(b, t, m["q"])
    h = h + ad(o, lw["wo"], "wo")
    x = nrm(h)
    f = jax.nn.silu(ad(x, lw["wg"], "wg")) * ad(x, lw["wu"], "wu")
    return h + ad(f, lw["wd"], "wd")


def readout(h, emb, cfg: dict, quant=None):
    """Final norm and tied readout: (..., d) -> (..., V) float32 logits."""
    return mm(norm_of(cfg)(h), emb.astype(jnp.float32).T, quant)


class Reference:
    """The reference, one configuration, one adapter and one seed: weights
    are made again layer by layer on every pass."""

    def __init__(self, cfg: dict, adapter: dict, seed: int, quant=None):
        other = {k: cfg[k] for k, ok in RUNS_AS.items()
                 if cfg.get(k, ok) != ok}
        if other:
            raise ValueError(f"{cfg['name']}: this reference has no {other}")
        self.cfg, self.adapter, self.quant = cfg, adapter, quant
        self.key = seed_key(seed)
        self.L = cfg["num_hidden_layers"]
        self._layer_w = jax.jit(functools.partial(W.layer, cfg))
        self._embed = jax.jit(functools.partial(W.embed, cfg))
        self._cores = jax.jit(functools.partial(W.cores, cfg, adapter))
        self._fwd = jax.jit(functools.partial(
            _layer_static, cfg=cfg, adapter=adapter, quant=quant))
        self._bwd = jax.jit(functools.partial(
            _layer_vjp, cfg=cfg, adapter=adapter, quant=quant))
        self._head = jax.jit(functools.partial(
            _head_loss, cfg=cfg, quant=quant))
        self._logits = jax.jit(functools.partial(
            _readout_static, cfg=cfg, quant=quant))

    def cores(self):
        return self._cores(self.key)

    def embed(self):
        return self._embed(self.key)

    def hidden(self, tokens, task, cores, keep: bool = False):
        """Final hidden states (B, T, d) of ``tokens`` (B, T); with
        ``keep`` also every layer's input."""
        emb = self.embed()
        h = emb[jnp.asarray(tokens)].astype(jnp.float32)
        del emb
        hs = [h] if keep else None
        for l in range(self.L):
            lw = self._layer_w(self.key, l)
            h = self._fwd(h, lw, cores, jnp.int32(l), task)
            del lw
            if keep:
                hs.append(h)
        return (h, hs) if keep else h

    def logits_at(self, h, rows, cols):
        """Logits of hidden rows ``h[rows, cols]`` (float32, (N, V))."""
        emb = self.embed()
        return self._logits(h[jnp.asarray(rows), jnp.asarray(cols)], emb)

    def loss_and_grads(self, tokens, mask, cores, chunk: int = 1024):
        """Mean next-token loss of ``tokens`` (B, T) and its gradient with
        respect to the TT cores (the frozen weights take none)."""
        tokens = jnp.asarray(tokens)
        mask = jnp.asarray(mask, jnp.float32)
        b, t = tokens.shape
        h, hs = self.hidden(tokens, None, cores, keep=True)
        emb = self.embed()
        # next-token targets: position p predicts token p+1, gated by
        # mask[p+1]; the last position predicts nothing
        tgt = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), jnp.int32)],
                              1)
        valid = jnp.concatenate([mask[:, 1:], jnp.zeros((b, 1))], 1)
        n_valid = jnp.sum(valid)
        hf = h.reshape(b * t, -1)
        tf, vf = tgt.reshape(-1), valid.reshape(-1)
        loss = 0.0
        dh = []
        for s in range(0, b * t, chunk):
            l_c, d_c = self._head(hf[s:s + chunk], emb, tf[s:s + chunk],
                                  vf[s:s + chunk])
            loss = loss + l_c
            dh.append(d_c)
        del emb
        loss = loss / n_valid
        dh = jnp.concatenate(dh).reshape(b, t, -1) / n_valid
        grads = [jnp.zeros_like(c) for c in cores]
        for l in reversed(range(self.L)):
            lw = self._layer_w(self.key, l)
            dh, dc = self._bwd(hs[l], lw, cores, jnp.int32(l), dh)
            grads = [g + d for g, d in zip(grads, dc)]
            del lw
            hs[l + 1] = None
        return loss, grads


def _layer_static(h, lw, cores, l, task, *, cfg, adapter, quant):
    return layer_fn(h, lw, cores, l, task, cfg, adapter, quant)


def _layer_vjp(h, lw, cores, l, dh, *, cfg, adapter, quant):
    _, vjp = jax.vjp(lambda x, c: layer_fn(x, lw, c, l, None, cfg, adapter,
                                           quant), h, cores)
    return vjp(dh)


def _readout_static(h, emb, *, cfg, quant):
    return readout(h, emb, cfg, quant)


def _head_loss(h, emb, tgt, valid, *, cfg, quant):
    """Summed cross entropy of one chunk of rows and its gradient in h."""
    def f(x):
        lg = readout(x, emb, cfg, quant)
        lse = jax.nn.logsumexp(lg, -1)
        true = jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]
        return jnp.sum((lse - true) * valid)
    return jax.value_and_grad(f)(h)
