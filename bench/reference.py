"""Plain float32 reference of the served decoder, for the ``correct`` check.

Written from the architecture's description (pre-norm decoder: RMSNorm,
RoPE split-half, grouped-query causal softmax attention with optional
per-head q/k RMSNorm, ReLU MLP gated or plain, final RMSNorm, linear head)
in straightforward ``jax.numpy`` at ``highest`` matmul precision, with the
dense MLP: every hidden row is computed.  It imports nothing of the
program.  Weights come from ``weights.Generator``, one layer at a time, so
the reference never holds the whole model; requests are run layer by layer,
each padded at its end to a multiple of ``bucket`` positions (causal, so the
padding changes no earlier position).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 128      # positions per head-projection call (one shape)
Q_BLOCK = 256         # query rows per attention block


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_layer_fn(m: dict):
    """h (S, d) float32 -> h after one decoder layer."""
    H, K, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    rep = H // K

    def layer(lw, h):
        S = h.shape[0]
        x = _rms(h, eps)
        q = (x @ lw["wq"]).reshape(S, H, hd)
        k = (x @ lw["wk"]).reshape(S, K, hd)
        v = (x @ lw["wv"]).reshape(S, K, hd)
        if m["qk_norm"]:
            q, k = _rms(q, eps), _rms(k, eps)
        pos = jnp.arange(S)
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
        qb = math.gcd(Q_BLOCK, S)

        def block(start):
            qs = jax.lax.dynamic_slice_in_dim(q, start, qb).reshape(
                qb, K, rep, hd)
            s = jnp.einsum("qkrh,tkh->krqt", qs, k) * hd ** -0.5
            causal = (start + jnp.arange(qb))[:, None] >= pos[None, :]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("krqt,tkh->qkrh", p, v).reshape(qb, H * hd)

        o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, H * hd)
        h = h + o @ lw["wo"]
        x = _rms(h, eps)
        a = jax.nn.relu(x @ lw["wg_t"].T)
        if "wu_t" in lw:
            a = a * (x @ lw["wu_t"].T)
        return h + a @ lw["wd_t"]

    return layer


def _head_block(hn, table, tok):
    """hn (P, d), table (V, d) bf16 -> best logit, token logit, argmax."""
    logits = hn @ table.astype(jnp.float32).T
    return (logits.max(-1), jnp.take_along_axis(logits, tok[:, None], -1)[:, 0],
            jnp.argmax(logits, -1).astype(jnp.int32))


def quantize(w, axis: int, fmt: str):
    """Weights in a lower format, per output channel (absmax over
    ``axis``, the input dimension), back as float32: the control."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if fmt == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if fmt == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control format {fmt!r}")


# input axis of each matrix: x @ w for (in, out), rows = neurons for _t
_IN_AXIS = {"wq": 0, "wk": 0, "wv": 0, "wo": 0, "wg_t": 1, "wu_t": 1,
            "wd_t": 0}


class Reference:
    """``fmt`` "" is the reference; "int8" / "fp8" compute it with every
    weight matrix (embedding and head included) in that format: the
    control of the check."""

    def __init__(self, m: dict, gen, bucket: int, fmt: str = ""):
        self.m, self.gen, self.bucket, self.fmt = m, gen, bucket, fmt
        self.times = {}
        q = (lambda w, axis: quantize(w, axis, fmt)) if fmt else (
            lambda w, axis: w)
        with jax.default_matmul_precision("highest"):
            layer = make_layer_fn(m)
            self._layer = jax.jit(lambda lw, h: layer(
                {k: q(v, _IN_AXIS[k]) for k, v in lw.items()}, h))
            g = gen.final_gain()
            self._norm = jax.jit(lambda h: _rms(h, m["norm_eps"]) * g)
            self._head = jax.jit(lambda hn, t, tok: _head_block(
                hn, q(t.astype(jnp.float32), 1), tok))
            self._embed = jax.jit(lambda t, ids: jnp.take(
                q(t.astype(jnp.float32), 1), ids, axis=0))

    def _tick(self, name, t0, x):
        jax.block_until_ready(x)
        self.times[name] = self.times.get(name, 0.0) + (
            time.perf_counter() - t0)

    def served(self, seed: int, prompts: list, outs: list,
               tokens: list = None) -> list:
        """Teacher-force each prompt with its served tokens.  For every
        served token returns (best logit, logit of the token, argmax), one
        (n_out, 3) array per request; ``tokens`` replaces the served
        tokens as the ones whose logit is read (the control's argmax)."""
        with jax.default_matmul_precision("highest"):
            return self._served(seed, prompts, outs,
                                outs if tokens is None else tokens)

    def _served(self, seed, prompts, outs, reads):
        b = self.bucket
        seqs, need = [], []
        for p, o in zip(prompts, outs):
            seq = np.concatenate([np.asarray(p, np.int32),
                                  np.asarray(o, np.int32)[:-1]])
            pad = -(-len(seq) // b) * b
            seqs.append(np.pad(seq, (0, pad - len(seq))))
            need.append(np.arange(len(p) - 1, len(seq)))
        t0 = time.perf_counter()
        table = self.gen.embed(seed)
        hs = [self._embed(table, jnp.asarray(s)) for s in seqs]
        del table
        self._tick("embed_s", t0, hs)
        for layer in range(self.m["n_layers"]):
            t0 = time.perf_counter()
            lw = self.gen.layer(seed, layer)
            self._tick("weights_s", t0, lw)
            t0 = time.perf_counter()
            hs = [self._layer(lw, h) for h in hs]
            self._tick("layers_s", t0, hs)
            del lw
        t0 = time.perf_counter()
        head = self.gen.head(seed)
        out = []
        for h, pos, o in zip(hs, need, reads):
            hn = self._norm(h)
            rows = []
            for s0 in range(0, len(pos), HEAD_BLOCK):
                idx = pos[s0:s0 + HEAD_BLOCK]
                tok = np.asarray(o, np.int32)[s0:s0 + HEAD_BLOCK]
                n = len(idx)
                idx = np.pad(idx, (0, HEAD_BLOCK - n), mode="edge")
                tok = np.pad(tok, (0, HEAD_BLOCK - n), mode="edge")
                best, got, arg = self._head(jnp.take(hn, jnp.asarray(idx),
                                                     axis=0),
                                            head, jnp.asarray(tok))
                rows.append(np.stack([np.asarray(best), np.asarray(got),
                                      np.asarray(arg, np.float64)],
                                     -1)[:n])
            out.append(np.concatenate(rows))
        self._tick("head_s", t0, head)
        return out
