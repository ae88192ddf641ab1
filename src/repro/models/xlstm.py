"""xLSTM blocks: chunkwise-parallel mLSTM + sequential sLSTM (arXiv:2405.04517).

mLSTM: matrix memory C (dk x dv) with exponential input gate and sigmoid-in-
log-space forget gate; chunkwise form keeps exact max-stabilization across
chunk boundaries.  sLSTM: scalar memory with true (nonlinear) recurrence on
h_{t-1} -> gates, computed with a lax.scan.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import Builder


PyTree = Any


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(b: Builder, *, d_model: int, num_heads: int,
               proj_factor: float = 2.0, conv_width: int = 4) -> PyTree:
    d_inner = int(d_model * proj_factor)
    return {
        "up": cm.dense_init(b, d_model, 2 * d_inner, ("embed", "ssm")),
        "conv": {"kernel": b.param((conv_width, d_inner), (None, "ssm"),
                                   scale=conv_width ** -0.5),
                 "bias": b.param((d_inner,), ("ssm",), init="zeros")},
        "wq": cm.dense_init(b, d_inner, d_inner, ("ssm", "qkv")),
        "wk": cm.dense_init(b, d_inner, d_inner, ("ssm", "qkv")),
        "wv": cm.dense_init(b, d_inner, d_inner, ("ssm", "qkv")),
        "w_if": cm.dense_init(b, d_inner, 2 * num_heads, ("ssm", None),
                              scale=0.01),
        "if_bias": b.param((2 * num_heads,), (None,), init="zeros"),
        "norm": {"scale": b.param((d_inner,), ("ssm",), init="zeros")},
        "down": cm.dense_init(b, d_inner, d_model, ("ssm", "embed")),
    }


def _mlstm_core_chunked(q, k, v, ig, fg, state, chunk: int):
    """q,k,v: (B,S,H,D); ig/fg raw gates: (B,S,H). state: (C,n,m) or None.
    Returns h (B,S,H,D), final state. Exact stabilized chunkwise form."""
    B, S, H, D = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    q = q.reshape(B, nc, chunk, H, D).astype(jnp.float32) * D ** -0.5
    k = k.reshape(B, nc, chunk, H, D).astype(jnp.float32)
    v = v.reshape(B, nc, chunk, H, D).astype(jnp.float32)
    ig = ig.reshape(B, nc, chunk, H).astype(jnp.float32)
    logf = jax.nn.log_sigmoid(fg.reshape(B, nc, chunk, H).astype(jnp.float32))
    F = jnp.cumsum(logf, axis=2)  # inclusive cumulative log-forget

    if state is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    ii = jnp.arange(chunk)
    causal = ii[:, None] >= ii[None, :]

    def step(carry, xs):
        Cp, np_, mp = carry
        qc, kc, vc, igc, Fc, logfc = xs  # (B,chunk,...)
        # log weight of source i at target t: b[t,i] = F_t - F_i + ig_i
        bmat = Fc[:, :, None, :] - Fc[:, None, :, :] + igc[:, None, :, :]
        bmat = jnp.where(causal[None, :, :, None], bmat, -jnp.inf)
        a = Fc + mp[:, None, :]  # inter-chunk log weight (B,chunk,H)
        m_row = jnp.maximum(jnp.max(bmat, axis=2), a)  # (B,chunk,H)
        w = jnp.exp(bmat - m_row[:, :, None, :])  # (B,t,i,H)
        s_inter = jnp.exp(a - m_row)  # (B,chunk,H)
        qk = jnp.einsum("bthd,bihd->btih", qc, kc)
        num = jnp.einsum("btih,btih,bihd->bthd", qk, w, vc)
        num = num + s_inter[..., None] * jnp.einsum("bthd,bhde->bthe", qc, Cp)
        den = jnp.einsum("btih,btih->bth", qk, w)
        den = den + s_inter * jnp.einsum("bthd,bhd->bth", qc, np_)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_row))[..., None]
        # chunk-end state
        FL = Fc[:, -1]  # (B,H)
        g_end = FL[:, None, :] - Fc + igc  # (B,chunk,H) log weight to end
        m_new = jnp.maximum(FL + mp, jnp.max(g_end, axis=1))
        wg = jnp.exp(g_end - m_new[:, None, :])
        C_new = jnp.exp(FL + mp - m_new)[:, :, None, None] * Cp + \
            jnp.einsum("bih,bihd,bihe->bhde", wg, kc, vc)
        n_new = jnp.exp(FL + mp - m_new)[..., None] * np_ + \
            jnp.einsum("bih,bihd->bhd", wg, kc)
        return (C_new, n_new, m_new), h

    xs = (q.transpose(1, 0, 2, 3, 4), k.transpose(1, 0, 2, 3, 4),
          v.transpose(1, 0, 2, 3, 4), ig.transpose(1, 0, 2, 3),
          F.transpose(1, 0, 2, 3), logf.transpose(1, 0, 2, 3))
    (Cf, nf, mf), hs = jax.lax.scan(step, (C0, n0, m0), xs)
    h = hs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)
    return h, (Cf, nf, mf)


def mlstm_core_step(q, k, v, ig, fg, state):
    """Single-token recurrent update. q,k,v: (B,H,D); gates (B,H)."""
    C, n, m = state
    D = q.shape[-1]
    qs = q.astype(jnp.float32) * D ** -0.5
    logf = jax.nn.log_sigmoid(fg.astype(jnp.float32))
    m_new = jnp.maximum(logf + m, ig.astype(jnp.float32))
    i_p = jnp.exp(ig - m_new)
    f_p = jnp.exp(logf + m - m_new)
    C = f_p[..., None, None] * C + i_p[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", k.astype(jnp.float32), v.astype(jnp.float32))
    n = f_p[..., None] * n + i_p[..., None] * k.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", qs, C)
    den = jnp.einsum("bhd,bhd->bh", qs, n)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h, (C, n, m_new)


def _mlstm_qkvg(p, x_mid, num_heads):
    B, S, d_inner = x_mid.shape
    D = d_inner // num_heads
    q = cm.dense(p["wq"], x_mid).reshape(B, S, num_heads, D)
    k = cm.dense(p["wk"], x_mid).reshape(B, S, num_heads, D)
    v = cm.dense(p["wv"], x_mid).reshape(B, S, num_heads, D)
    gates = cm.dense(p["w_if"], x_mid) + p["if_bias"].astype(cm.COMPUTE_DTYPE)
    ig, fg = gates[..., :num_heads], gates[..., num_heads:]
    return q, k, v, ig, fg


def _mlstm_out(p, h, z, B, S, d_inner):
    h = h.reshape(B, S, d_inner).astype(z.dtype)
    h = cm.rmsnorm(p["norm"], h)
    return cm.dense(p["down"], h * jax.nn.silu(z))


def mlstm_apply_full(p: PyTree, x: jax.Array, *, num_heads: int,
                     chunk: int = 256, return_state: bool = False,
                     ) -> tuple[jax.Array, PyTree | None]:
    B, S, _ = x.shape
    d_inner = p["conv"]["bias"].shape[0]
    up = cm.dense(p["up"], x)
    x_in, z = up[..., :d_inner], up[..., d_inner:]
    from repro.models.ssm import _conv_full
    x_mid = _conv_full(p, x_in)
    q, k, v, ig, fg = _mlstm_qkvg(p, x_mid, num_heads)
    # pad to chunk multiple: no-input (ig=-inf), no-forget (fg=+inf) steps
    ch = min(chunk, S)
    S_pad = -(-S // ch) * ch
    if S_pad != S:
        pq = ((0, 0), (0, S_pad - S), (0, 0), (0, 0))
        pg = ((0, 0), (0, S_pad - S), (0, 0))
        q, k, v = jnp.pad(q, pq), jnp.pad(k, pq), jnp.pad(v, pq)
        ig = jnp.pad(ig, pg, constant_values=-1e30)
        fg = jnp.pad(fg, pg, constant_values=30.0)
    h, state = _mlstm_core_chunked(q, k, v, ig, fg, None, ch)
    h = h[:, :S]
    out = _mlstm_out(p, h, z, B, S, d_inner)
    st = None
    if return_state:
        W = p["conv"]["kernel"].shape[0]
        st = {"C": state[0], "n": state[1], "m": state[2],
              "conv": x_in[:, S - (W - 1):].astype(jnp.bfloat16)}
    return out, st


def mlstm_init_state(batch: int, *, d_inner: int, num_heads: int,
                     conv_width: int = 4) -> PyTree:
    D = d_inner // num_heads
    return {
        "C": jnp.zeros((batch, num_heads, D, D), jnp.float32),
        "n": jnp.zeros((batch, num_heads, D), jnp.float32),
        "m": jnp.full((batch, num_heads), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), jnp.bfloat16),
    }


def mlstm_apply_decode(p: PyTree, x: jax.Array, state: PyTree, *,
                       num_heads: int) -> tuple[jax.Array, PyTree]:
    B = x.shape[0]
    d_inner = p["conv"]["bias"].shape[0]
    up = cm.dense(p["up"], x)
    x_in, z = up[..., :d_inner], up[..., d_inner:]
    hist = jnp.concatenate([state["conv"].astype(x_in.dtype), x_in], axis=1)
    w = p["conv"]["kernel"].astype(x_in.dtype)
    conv_out = jnp.einsum("bwc,wc->bc", hist, w) + p["conv"]["bias"].astype(x_in.dtype)
    x_mid = jax.nn.silu(conv_out)[:, None]
    q, k, v, ig, fg = _mlstm_qkvg(p, x_mid, num_heads)
    h, (C, n, m) = mlstm_core_step(q[:, 0], k[:, 0], v[:, 0], ig[:, 0],
                                   fg[:, 0], (state["C"], state["n"], state["m"]))
    out = _mlstm_out(p, h[:, None], z, B, 1, d_inner)
    return out, {"C": C, "n": n, "m": m, "conv": hist[:, 1:].astype(jnp.bfloat16)}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_init(b: Builder, *, d_model: int, num_heads: int,
               ff_factor: float = 4.0 / 3.0) -> PyTree:
    hd = d_model // num_heads
    d_ff = int(d_model * ff_factor)
    return {
        # input projections for gates z,i,f,o
        "w_in": cm.dense_init(b, d_model, 4 * d_model, ("embed", "ssm")),
        # block-diagonal recurrent weights per head: (H, hd, 4*hd)
        "r": {"kernel": b.param((num_heads, hd, 4 * hd), (None, None, None),
                                scale=hd ** -0.5)},
        "gate_bias": b.param((4 * d_model,), (None,), init="zeros"),
        "norm": {"scale": b.param((d_model,), ("embed_act",), init="zeros")},
        "ff_up": cm.dense_init(b, d_model, 2 * d_ff, ("embed", "mlp")),
        "ff_down": cm.dense_init(b, d_ff, d_model, ("mlp", "embed")),
    }


def _slstm_step(carry, g_t, r, num_heads):
    c, n, m, h_prev = carry  # each (B, H, hd)
    B = g_t.shape[0]
    hd = c.shape[-1]
    rec = jnp.einsum("bhd,hde->bhe", h_prev, r)  # (B,H,4*hd)
    g = g_t.reshape(B, num_heads, 4, hd).transpose(0, 1, 3, 2)
    g = g + rec.reshape(B, num_heads, hd, 4)
    zt = jnp.tanh(g[..., 0])
    it = g[..., 1]
    ft = g[..., 2]
    ot = jax.nn.sigmoid(g[..., 3])
    logf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(logf + m, it)
    i_p = jnp.exp(it - m_new)
    f_p = jnp.exp(logf + m - m_new)
    c_new = f_p * c + i_p * zt
    n_new = jnp.maximum(f_p * n + i_p, 1e-6)
    h = ot * c_new / n_new
    return (c_new, n_new, m_new, h), h


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _slstm_scan(gates_in, state_tuple, r, num_heads, axis_names):
    """Sequential sLSTM scan with hand-written BPTT.

    Plain autodiff-of-scan under shard_map transposes the per-step varying
    cast
    of the replicated recurrent weight R into a per-timestep psum of dR
    (4.7 MB x seq_len x layers - the xlstm train collective bottleneck).
    The custom VJP accumulates dR locally in the reverse scan's carry and
    psums ONCE over `axis_names` at the end.
    """
    out, _ = _slstm_fwd(gates_in, state_tuple, r, num_heads, axis_names)
    return out


def _slstm_fwd(gates_in, state_tuple, r, num_heads, axis_names):
    B, S, d4 = gates_in.shape
    d = d4 // 4
    rf = r.astype(jnp.float32)
    if axis_names:  # shard_map: make R device-varying ONCE so its per-step
        # cotangents stay local
        rf = jax.lax.pcast(rf, axis_names, to="varying")
    gates_seq = gates_in.astype(jnp.float32).transpose(1, 0, 2)

    def step(carry, g_t):
        new_carry, h = _slstm_step(carry, g_t, rf, num_heads)
        return new_carry, (carry, h)  # save pre-step state for BPTT

    final, (saved_states, hs) = jax.lax.scan(step, state_tuple, gates_seq)
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, d)
    return (h, final), (gates_seq, saved_states, r)


def _slstm_bwd(num_heads, axis_names, res, cots):
    gates_seq, saved_states, r = res
    dh_out, dfinal = cots
    S, B, d4 = gates_seq.shape
    d = d4 // 4
    rf = r.astype(jnp.float32)
    if axis_names:
        rf = jax.lax.pcast(rf, axis_names, to="varying")
    dh_seq = dh_out.reshape(B, S, num_heads, d // num_heads) \
        .transpose(1, 0, 2, 3).astype(jnp.float32)
    dR0 = jnp.zeros(r.shape, jnp.float32)
    if axis_names:
        dR0 = jax.lax.pcast(dR0, axis_names, to="varying")

    def back(carry, xs):
        dstate, dR = carry
        g_t, st_prev, dh_t = xs
        _, vjp_fn = jax.vjp(
            lambda st, g, rr: _slstm_step(st, g, rr, num_heads),
            st_prev, g_t, rf)
        dc, dn, dm, dh = dstate
        dst_prev, dg, dr = vjp_fn(((dc, dn, dm, dh + dh_t),
                                   jnp.zeros_like(dh_t)))
        # h cotangent of this step's OUTPUT was already folded in; the
        # scan output h equals the carry h, so route dh via the carry.
        return (dst_prev, dR + dr), dg

    (dstate0, dR), dg_seq = jax.lax.scan(
        back, (dfinal, dR0), (gates_seq, saved_states, dh_seq), reverse=True)
    if axis_names:
        dR = jax.lax.psum(dR, axis_names)
    dgates = dg_seq.transpose(1, 0, 2).astype(jnp.float32)
    return dgates, dstate0, dR.astype(r.dtype)


_slstm_scan.defvjp(_slstm_fwd, _slstm_bwd)


def slstm_core(p: PyTree, gates_in: jax.Array, state: PyTree, *,
               num_heads: int):
    """Dispatch the sequential scan, under shard_map when rules are active
    (batch-local recurrence; ONE dR psum at the end via the custom VJP)."""
    from repro.dist.axes import current_rules
    init = (state["c"], state["n"], state["m"], state["h"])
    rules = current_rules()
    B = gates_in.shape[0]
    axis_names: tuple = ()
    wrap = None
    if rules is not None:
        batch_axes = rules.rules.get("batch") or ()
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        batch_axes = tuple(a for a in batch_axes
                           if a in rules.mesh.axis_names)
        dp = 1
        for a in batch_axes:
            dp *= rules.mesh.shape[a]
        if batch_axes and B % dp == 0 and B >= dp:
            axis_names = batch_axes
            wrap = rules.mesh

    def core_fn(g, st, r):
        return _slstm_scan(g, st, r, num_heads, axis_names)

    fn = core_fn
    if wrap is not None:
        from jax.sharding import PartitionSpec as P
        bsp = P(axis_names, None, None)
        fn = jax.shard_map(core_fn, mesh=wrap,
                            in_specs=(bsp, (bsp,) * 4, P(None, None, None)),
                            out_specs=(bsp, (bsp,) * 4))
    h, (c, n, m, h_last) = fn(gates_in.astype(jnp.float32), init,
                              p["r"]["kernel"])
    return h, {"c": c, "n": n, "m": m, "h": h_last}


def slstm_init_state(batch: int, *, d_model: int, num_heads: int) -> PyTree:
    hd = d_model // num_heads
    z = jnp.zeros((batch, num_heads, hd), jnp.float32)
    return {"c": z, "n": z + 1e-6, "m": z - 1e30, "h": z}


def slstm_apply(p: PyTree, x: jax.Array, state: PyTree | None, *,
                num_heads: int, return_state: bool = False,
                ) -> tuple[jax.Array, PyTree | None]:
    B, S, d = x.shape
    if state is None:
        state = slstm_init_state(B, d_model=d, num_heads=num_heads)
    gates_in = cm.dense(p["w_in"], x) + p["gate_bias"].astype(cm.COMPUTE_DTYPE)
    h, new_state = slstm_core(p, gates_in, state, num_heads=num_heads)
    h = cm.rmsnorm(p["norm"], h.astype(x.dtype))
    ff = cm.dense(p["ff_up"], h)
    d_ff = ff.shape[-1] // 2
    h = cm.dense(p["ff_down"], jax.nn.gelu(ff[..., :d_ff], approximate=True)
                 * ff[..., d_ff:])
    return h, (new_state if return_state else None)
