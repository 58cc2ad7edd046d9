"""Plain reference of the latent-attention MoE decoder (Kimi K2's block,
the DeepSeek-V3 block) with a MetaTT adapter.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one layer
at a time, with no kernel, cache, sorting or batching of the program under
test, and nothing imported from it: the weights come from ``weights.py``
beside this file, made again from the seed layer by layer.

The block, as published (the configuration file's ``departures`` say
where this differs):

    h = x + MLA(RMSNorm(x)) ;  y = h + FFN(RMSNorm(h))
    MLA:  q = q_b(RMSNorm(q_a(x)))               per head [q_nope 128, q_pe 64]
          [c, k_pe] = kv_a(x)                     c 512 wide, k_pe 64, one head
          [k_nope, v] = kv_b(RMSNorm(c))          per head 128 + 128
          q = [q_nope, rope(q_pe)], k = [k_nope, rope(k_pe)] (k_pe shared)
          o = causal softmax(q·kᵀ · scale) v ;  out = o_proj(o)
          rope: YaRN frequencies (``yarn_inv_freq``), pairs as halves, cos
          and sin times mscale(s, mscale) / mscale(s, mscale_all_dim);
          scale = qk_head_dim^-0.5 · mscale(s, mscale_all_dim)²,
          mscale(s, m) = 0.1·m·ln s + 1; RMSNorm eps rms_norm_eps, gain 1.
    FFN:  layers < first_k_dense_replace: SwiGLU of intermediate_size;
          after them: the expert layer,
            s = sigmoid(x·W_r) over all experts_total experts (f32)
            chosen = top-k of s + b_corr
            weight = s[chosen] / (Σ s[chosen] + 1e-20)
                     · routed_scaling_factor
            y = SwiGLU_shared(x) + Σ_{chosen e held here} weight_e SwiGLU_e(x)
          where the held experts are [expert_offset, + n_routed_experts):
          experts held elsewhere are left out, as on the chip.
    Readout: final RMSNorm, then the untied head (the sliced vocabulary).

Routing ties: the program routes on bf16 hidden states and this reference
on f32 ones, so where a token's 8th- and 9th-ranked biased scores lie
within ``TIE_MARGIN`` of each other, either choice is a sound reading:
such a token is a tie. Where the program hands over its routing
(``follow``: each training step's chosen experts, layer by layer, which
the program returns when asked to), the reference takes the program's
choice wherever every expert of it lies within ``TIE_MARGIN`` of the
reference's own 8th biased score, and its own choice elsewhere; so a
tie never shows as a gap, while a choice further off does. It counts the
ties, the ties routed as the program did where that differs from its own
choice, and the choices it refused, and logs them (``Reference.ties``).
``TIE_MARGIN`` is 5e-3: the program's router input carries the bf16
rounding of every layer before it, about 1% of a unit-variance router
logit, and sigmoid's slope at the scores that compete for the 8th place
(about 0.1) makes that about 1e-3 in score; the margin is five times
that, and under the spacing of neighbouring scores there (about 5e-3 for
384 experts), so a router that chooses by other scores than the
published ones is refused.

The adapter on matrix m of layer l adds alpha · x A[:d_in] B[:, :d_out]
(``dense_decoder.reference.tt_factors``). ``quant``: None for the float32
reference; "fp8" (the control) rounds every bf16 matmul's operands to
float8 (e4m3 forward, e5m2 gradients; ``dense_decoder.reference``) and
the float32 router's to bfloat16, the precision below each.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.report import log
from bench.lib.weights import seed_key
from bench.models.dense_decoder.reference import HI, _bf16, _q, mm, \
    tt_factors

from . import weights as W

TIE_MARGIN = 5e-3
#: the program's routing of its training steps in order, each (expert
#: layers, tokens, k) global expert ids; set by ``follow``
_PROGRAM_ROUTING: list = []


def follow(routing) -> None:
    """Hand over the program's routing of its training steps, in order:
    each an array (expert layers, tokens, k) of global expert ids. A
    ``Reference`` made afterwards follows step s's at the ties of its s-th
    ``loss_and_grads``. A module-level hand-over, because the harness
    makes the Reference from the configuration, adapter and seed alone; a
    stale one can do no harm, since it is followed only where its choice
    lies within the margin, where either choice is sound."""
    _PROGRAM_ROUTING[:] = [jnp.asarray(r, jnp.int32) for r in routing]


def rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn_inv_freq(d: int, base: float, rs: dict):
    """(d/2,) rotary frequencies under YaRN (the published steps)."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = base ** (-2.0 * i / d)
    orig = rs["original_max_position_embeddings"]

    def corr(b):
        return d * math.log(orig / (2 * math.pi * b)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(x, t: int, cfg: dict):
    """x (B, T, n, d_rope) at positions 0..T-1; pairs as halves."""
    rs = cfg["rope_scaling"]
    d = x.shape[-1]
    inv = yarn_inv_freq(d, float(cfg["rope_theta"]), rs)
    amp = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    cos, sin = cos[None, :, None], sin[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, scale: float, quant=None, block: int = 256):
    """Causal attention, q, k (B, T, H, dqk), v (B, T, H, dv). Query blocks
    run one at a time under ``checkpoint``, so the (T, T) scores are never
    whole in memory, nor kept for the backward."""
    b, t, h, _ = q.shape

    @jax.checkpoint
    def one(qb, pb):
        s = jnp.einsum("bthd,bshd->bhts", _q(qb, quant), _q(k, quant),
                       precision=HI) * scale
        s = jnp.where(pb[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", _q(p, quant), _q(v, quant, 1),
                          precision=HI)

    nblk = -(-t // block)
    if nblk == 1:
        return one(q, jnp.arange(t))
    pad = nblk * block - t
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = qs.reshape(b, nblk, block, h, -1).transpose(1, 0, 2, 3, 4)
    pos = jnp.arange(nblk * block).reshape(nblk, block)
    out = jax.lax.map(lambda a: one(*a), (qs, pos))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, nblk * block, h,
                                               -1)[:, :t]


def _adapted(x, w, leaf, fac, adapter, quant):
    y = mm(x, w, quant)
    name = {v: k for k, v in W.MATRIX_LEAF.items()}[leaf]
    if name not in adapter["matrices"]:
        return y
    a, b = fac
    a = a[adapter["matrices"].index(name)][:x.shape[-1]]
    return y + adapter["alpha"] * mm(mm(x, a, quant), b[:, :w.shape[1]],
                                     quant)


def mla(x, lw, fac, cfg: dict, adapter: dict, quant=None):
    m = W.dims(cfg)
    b, t, _ = x.shape
    h, dn, dr, dv = m["h"], m["dn"], m["dr"], m["dv"]
    eps = cfg["rms_norm_eps"]
    ad = functools.partial(_adapted, fac=fac, adapter=adapter, quant=quant)
    q = ad(rmsnorm(ad(x, lw["wq_a"], "wq_a"), eps), lw["wq_b"], "wq_b")
    q = q.reshape(b, t, h, dn + dr)
    ckv = ad(x, lw["wkv_a"], "wkv_a")
    kv = ad(rmsnorm(ckv[..., :m["rkv"]], eps), lw["wkv_b"], "wkv_b")
    kv = kv.reshape(b, t, h, dn + dv)
    k_pe = rope(ckv[..., m["rkv"]:][:, :, None], t, cfg)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], t, cfg)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, t, h, dr))], -1)
    o = attend(q, k, kv[..., dn:], softmax_scale(cfg), quant)
    return ad(o.reshape(b, t, h * dv), lw["wo"], "wo")


def swiglu(x, wg, wu, wd, quant=None):
    return mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd, quant)


def route(x, lw, cfg: dict, quant=None, prog=None, use=False):
    """(weights (N, E) of the chosen experts, zero elsewhere; counts
    (ties, ties routed as the program did, program choices refused)).
    prog (N, k): the program's chosen experts, followed where ``use`` and
    every one lies within ``TIE_MARGIN`` of the own 8th biased score."""
    m = W.dims(cfg)
    k = m["k"]
    wr = lw["router"]
    if quant is not None:                     # f32 router: bf16 below it
        x, wr = _bf16(x), _bf16(wr)
    s = jax.nn.sigmoid(jnp.matmul(x, wr, precision=HI))
    sb = s + lw["router_bias"]
    top, idx = jax.lax.top_k(sb, k + 1)
    own = idx[:, :k]
    tie = top[:, k - 1] - top[:, k] < TIE_MARGIN
    ok = jnp.zeros_like(tie)
    if prog is not None:
        ok = use & (jnp.take_along_axis(sb, prog, -1).min(-1)
                    >= top[:, k - 1] - TIE_MARGIN)
        differs = jnp.any(jnp.sort(prog, -1) != jnp.sort(own, -1), -1)
        pick = jnp.where(ok[:, None], prog, own)
    else:
        differs, pick = jnp.zeros_like(tie), own
    chosen = jnp.take_along_axis(s, pick, -1)
    wts = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    full = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                pick].set(wts)
    counts = jnp.stack([jnp.sum(tie), jnp.sum(ok & differs),
                        jnp.sum(use & differs & ~ok)])
    return full, counts


def ffn(x, lw, cfg: dict, dense: bool, quant=None, prog=None, use=False):
    if dense:
        return swiglu(x, lw["wg"], lw["wu"], lw["wd"], quant), \
            jnp.zeros((3,), jnp.int32)
    m = W.dims(cfg)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    full, counts = route(x, lw, cfg, quant, prog, use)
    y = swiglu(x, lw["s_wg"], lw["s_wu"], lw["s_wd"], quant)
    for e in range(m["held"]):
        g = full[:, m["lo"] + e][:, None]
        y = y + g * swiglu(x, lw["e_wg"][e], lw["e_wu"][e], lw["e_wd"][e],
                           quant)
    return y.reshape(shape), counts


def layer_fn(h, lw, cores, l, cfg: dict, adapter: dict, dense: bool,
             quant=None, prog=None, use=False):
    """One block: (B, T, d) float32 -> ((B, T, d), routing counts)."""
    eps = cfg["rms_norm_eps"]
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    fac = tt_factors(cores, adapter, l, None)
    h = h + mla(rmsnorm(h, eps), lw, fac, cfg, adapter, quant)
    y, counts = ffn(rmsnorm(h, eps), lw, cfg, dense, quant, prog, use)
    return h + y, counts


def readout(h, head, cfg: dict, quant=None):
    return mm(rmsnorm(h, cfg["rms_norm_eps"]), head.astype(jnp.float32).T,
              quant)


def _own_routing(n_tokens: int, k: int):
    """No program routing: every layer routes on its own scores."""
    none = jnp.zeros((n_tokens, k), jnp.int32)
    return lambda l: (none, jnp.bool_(False))


class Reference:
    """The reference, one configuration, one adapter and one seed: weights
    are made again layer by layer on every pass. ``ties`` sums the routing
    counts of the forward passes so far: (ties, ties routed as the program
    did where that differs from the own choice, program choices refused).
    It follows the routing handed over by ``follow`` before it was made."""

    def __init__(self, cfg: dict, adapter: dict, seed: int, quant=None):
        if adapter["variant"] != "4d":
            raise ValueError(f"{cfg['name']}: this reference has no "
                             f"adapter variant {adapter['variant']!r}")
        self.cfg, self.adapter, self.quant = cfg, adapter, quant
        self.key = seed_key(seed)
        self.L = cfg["num_hidden_layers"]
        self.ties = [0, 0, 0]
        self._program = list(_PROGRAM_ROUTING)
        self._passes = 0
        self._layer_w = jax.jit(functools.partial(W.layer, cfg),
                                static_argnames=("dense",))
        self._embed = jax.jit(functools.partial(W.embed, cfg))
        self._head_w = jax.jit(functools.partial(W.head, cfg))
        self._cores = jax.jit(functools.partial(W.cores, cfg, adapter))
        self._fwd = jax.jit(functools.partial(
            _layer_static, cfg=cfg, adapter=adapter, quant=quant),
            static_argnames=("dense",))
        self._bwd = jax.jit(functools.partial(
            _layer_vjp, cfg=cfg, adapter=adapter, quant=quant),
            static_argnames=("dense",))
        self._head = jax.jit(functools.partial(
            _head_loss, cfg=cfg, quant=quant))
        self._logits = jax.jit(functools.partial(
            _readout_static, cfg=cfg, quant=quant))

    def cores(self):
        return self._cores(self.key)

    def embed(self):
        return self._embed(self.key)

    def _w(self, l: int):
        dense = W.is_dense(self.cfg, l)
        return self._layer_w(self.key, l, dense=dense), dense

    def _routing(self, n_tokens: int):
        """The program's routing of the next training pass, as a function
        of the layer: (prog (N, k), use)."""
        rec = (self._program[self._passes]
               if self._passes < len(self._program) else None)
        self._passes += 1
        k = self.cfg["num_experts_per_tok"]
        n_dense = self.cfg["first_k_dense_replace"]
        want = (self.L - n_dense, n_tokens, k)
        if rec is None or tuple(rec.shape) != want:
            log(f"reference: no program routing of shape {want} for this "
                f"pass; routing on its own scores")
            return _own_routing(n_tokens, k)
        return lambda l: (rec[max(l - n_dense, 0)], jnp.bool_(True))

    def hidden(self, tokens, task, cores, keep: bool = False, routing=None):
        """Final hidden states (B, T, d) of ``tokens`` (B, T); with
        ``keep`` also every layer's input. ``routing``: the program's, as
        ``_routing`` gives it, or None."""
        emb = self.embed()
        h = emb[jnp.asarray(tokens)].astype(jnp.float32)
        del emb
        n = h.shape[0] * h.shape[1]
        if routing is None:
            routing = _own_routing(n, self.cfg["num_experts_per_tok"])
        hs = [h] if keep else None
        counts = np.zeros(3, np.int64)
        for l in range(self.L):
            lw, dense = self._w(l)
            h, c = self._fwd(h, lw, cores, jnp.int32(l), *routing(l),
                             dense=dense)
            counts += np.asarray(c, np.int64)
            del lw
            if keep:
                hs.append(h)
        self.ties = [a + int(b) for a, b in zip(self.ties, counts)]
        log(f"reference routing (margin {TIE_MARGIN}) over {n} tokens x "
            f"{self.L - self.cfg['first_k_dense_replace']} expert layers: "
            f"{counts[0]} ties, {counts[1]} routed as the program did "
            f"where its own choice differs, {counts[2]} program choices "
            f"beyond the margin refused")
        return (h, hs) if keep else h

    def logits_at(self, h, rows, cols):
        """Logits of hidden rows ``h[rows, cols]`` (float32, (N, V))."""
        return self._logits(h[jnp.asarray(rows), jnp.asarray(cols)],
                            self._head_w(self.key))

    def loss_and_grads(self, tokens, mask, cores, chunk: int = 1024):
        """Mean next-token loss of ``tokens`` (B, T) and its gradient with
        respect to the TT cores (the frozen weights take none); the s-th
        call follows the program's routing of its s-th step at ties."""
        tokens = jnp.asarray(tokens)
        mask = jnp.asarray(mask, jnp.float32)
        b, t = tokens.shape
        routing = self._routing(b * t)
        h, hs = self.hidden(tokens, None, cores, keep=True, routing=routing)
        head = self._head_w(self.key)
        # position p predicts token p+1, gated by mask[p+1]
        tgt = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), jnp.int32)],
                              1)
        valid = jnp.concatenate([mask[:, 1:], jnp.zeros((b, 1))], 1)
        n_valid = jnp.sum(valid)
        hf = h.reshape(b * t, -1)
        tf, vf = tgt.reshape(-1), valid.reshape(-1)
        loss, dh = 0.0, []
        for s in range(0, b * t, chunk):
            l_c, d_c = self._head(hf[s:s + chunk], head, tf[s:s + chunk],
                                  vf[s:s + chunk])
            loss = loss + l_c
            dh.append(d_c)
        del head
        loss = loss / n_valid
        dh = jnp.concatenate(dh).reshape(b, t, -1) / n_valid
        grads = [jnp.zeros_like(c) for c in cores]
        for l in reversed(range(self.L)):
            lw, dense = self._w(l)
            dh, dc = self._bwd(hs[l], lw, cores, jnp.int32(l), *routing(l),
                               dh, dense=dense)
            grads = [g + d for g, d in zip(grads, dc)]
            del lw
            hs[l + 1] = None
        return loss, grads


def _layer_static(h, lw, cores, l, prog, use, *, cfg, adapter, quant,
                  dense):
    return layer_fn(h, lw, cores, l, cfg, adapter, dense, quant, prog, use)


def _layer_vjp(h, lw, cores, l, prog, use, dh, *, cfg, adapter, quant,
               dense):
    _, vjp = jax.vjp(lambda x, c: layer_fn(x, lw, c, l, cfg, adapter, dense,
                                           quant, prog, use)[0], h, cores)
    return vjp(dh)


def _readout_static(h, head, *, cfg, quant):
    return readout(h, head, cfg, quant)


def _head_loss(h, head, tgt, valid, *, cfg, quant):
    """Summed cross entropy of one chunk of rows and its gradient in h."""
    def f(x):
        lg = readout(x, head, cfg, quant)
        lse = jax.nn.logsumexp(lg, -1)
        true = jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]
        return jnp.sum((lse - true) * valid)
    return jax.value_and_grad(f)(h)
