"""Power retention: the sequence mixer of the retention decoder block,
in plain ``jax.numpy``.

A softmax block keeps every key and value it has seen; a retention
block keeps ONE fixed-size state per KV head and sequence. With the
power ``p = 2`` and a per-head gate ``g_t`` in (0, 1), query head ``i``
of KV group ``i // groups`` reads

    a_tj = (q_t . k_j)^2 * prod_{l=j+1..t} g_l        (j <= t)
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

which equals the recurrence ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``,
``z_t = g_t z_{t-1} + phi(k_t)``, ``y_t = phi(q_t)^T S_t /
(phi(q_t)^T z_t + eps)`` with ``phi`` the symmetric second power,
``phi(a) . phi(b) = (a . b)^2``: the ``d (d + 1) / 2`` products
``a_i a_j``, ``i <= j`` (8,256 for a 128-wide key). Three forms of the
same function live here: :func:`retention_chunk` (inside a chunk the
attention form, across chunks the state: the training forward, every
prefill), :func:`retention_step` (one token: dense ``generate()``) and
:func:`retention_attention` (the attention form alone, the tests'
yardstick). The decode step's kernel over the paged pool is
``ops.pallas_kernels.retention_decode``.

**The state as it is stored** (every form here and the kernel agree on
it, so a state written by one is read by any other). The products are
laid out for the TPU's (8, 128) float32 tiles: the key's ``d``
features form ``d / 8`` blocks of 8; the pair of blocks ``(I, J)``,
``I <= J``, takes 64 consecutive rows, row ``8 a + c`` holding the pair
``i = 8 I + a``, ``j = 8 J + c``; the pairs of blocks run ``J`` outer,
``I`` inner. That is ``64 * nb (nb + 1) / 2`` rows (:func:`state_rows`;
8,704 for ``d = 128``, 5% over the logical 8,256: in a diagonal block
the rows with ``i > j`` repeat their mirror images and carry weight
0). The WRITE side carries the multiplicity (:func:`phi_write`: 1 on
the diagonal, 2 above it, 0 below) and the READ side the plain
products (:func:`phi_read`), so ``phi_read(q) . phi_write(k) =
(q . k)^2`` exactly as ``phi(q) . phi(k)`` does. The normaliser
``z`` is stored as the symmetric matrix ``Z = sum_j decay * k_j
k_j^T`` (``[d, d]``; ``phi(q)^T z = q^T Z q``), which the kernel
updates in one tile-aligned pass. States are float32 whatever the
compute dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: added to the normaliser; the sum of weights of a live position is
#: of the order of ``d`` (unit-RMS keys), so this only guards 0 / 0
RETENTION_EPS = 1e-6
#: positions a chunk of the training forward holds (inside a chunk the
#: attention form, C x C weights a head; across chunks the state)
TRAIN_CHUNK = 64
#: the gate's bias at init: g = sigmoid(5) = 0.993, a memory of some
#: 150 positions, where a zero bias would forget in two. The layer and
#: the benchmark's builder both start from it
GATE_BIAS_INIT = 5.0

_HIGHEST = jax.lax.Precision.HIGHEST


def state_rows(d: int) -> int:
    """Rows of the stored state per KV head for ``d``-wide keys."""
    if d % 8:
        raise ValueError(f"head_dim={d} must be a multiple of 8 (the "
                         "state is laid out in 8-row tiles)")
    nb = d // 8
    return 64 * nb * (nb + 1) // 2


def logical_state_rows(d: int) -> int:
    """``d (d + 1) / 2``: the symmetric second power's own size."""
    return d * (d + 1) // 2


@functools.lru_cache(maxsize=None)
def _layout(d: int):
    """(one-hot ``[d, rows]`` of each row's ``i``, of its ``j``, and
    the write side's weight ``[rows]``), as numpy constants."""
    nb = d // 8
    rows = state_rows(d)
    i_idx = np.zeros(rows, np.int64)
    j_idx = np.zeros(rows, np.int64)
    r = 0
    for jb in range(nb):
        for ib in range(jb + 1):
            for a in range(8):
                for c in range(8):
                    i_idx[r], j_idx[r] = 8 * ib + a, 8 * jb + c
                    r += 1
    w2 = np.where(i_idx < j_idx, 2.0, np.where(i_idx == j_idx, 1.0, 0.0))
    e_i = np.zeros((d, rows), np.float32)
    e_j = np.zeros((d, rows), np.float32)
    e_i[i_idx, np.arange(rows)] = 1.0
    e_j[j_idx, np.arange(rows)] = 1.0
    return e_i, e_j, w2.astype(np.float32)


def _pick(x, onehot):
    """``x[..., idx]`` as a product with a one-hot matrix: exact (one
    term a column; a bf16 operand needs one pass, a float32 one the
    highest precision) and MXU work, where a gather along the minor
    dimension is neither on the TPU."""
    return jnp.einsum("...d,dr->...r", x, jnp.asarray(onehot, x.dtype),
                      precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def phi_read(q):
    """``[..., d] -> [..., rows]`` float32: the plain products
    ``q_i q_j`` in the stored order."""
    e_i, e_j, _ = _layout(q.shape[-1])
    return _pick(q, e_i) * _pick(q, e_j)


def phi_write(k):
    """``[..., d] -> [..., rows]`` float32: the products with their
    multiplicity (diagonal 1, above it 2, mirror images 0)."""
    return phi_read(k) * jnp.asarray(_layout(k.shape[-1])[2])


def zero_state(batch: int, n_kv: int, d: int):
    """The state of an empty context: ``(S [B, Hkv, rows, d],
    Z [B, Hkv, d, d])`` float32."""
    return (jnp.zeros((batch, n_kv, state_rows(d), d), jnp.float32),
            jnp.zeros((batch, n_kv, d, d), jnp.float32))


def log_gate(gamma):
    """``log g = -softplus(-gamma)``, float32: ``g`` in (0, 1)."""
    return -jax.nn.softplus(-gamma.astype(jnp.float32))


def head_norm(x, gain, eps):
    """RMSNorm over one head's features (the QK-norm), in float32,
    returned in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


def project(mha, h, n_heads: int, n_kv: int, rotate, eps):
    """Rows ``h [N, F]`` of the block's first norm to the mixer's
    operands: ``q [N, H, d]``, ``k, v [N, Hkv, d]`` (q and k per-head
    normalised, then rotated by ``rotate``) and ``log g [N, Hkv]``
    float32. ONE derivation for the training forward, ``generate()``,
    prefill and the paged step."""
    n = h.shape[0]
    q = (h @ mha["Wq"]).reshape(n, n_heads, -1)
    k = (h @ mha["Wk"]).reshape(n, n_kv, -1)
    v = (h @ mha["Wv"]).reshape(n, n_kv, -1)
    q = rotate(head_norm(q, mha["q_gamma"], eps))
    k = rotate(head_norm(k, mha["k_gamma"], eps))
    return q, k, v, log_gate(h @ mha["Wgate"] + mha["bgate"])


def retention_attention(q, k, v, log_g, eps: float = RETENTION_EPS):
    """The attention form over whole sequences, nothing else: ``q``
    [B, T, H, d], ``k``/``v`` [B, T, Hkv, d], ``log_g`` [B, T, Hkv].
    Quadratic in T; the forms below are held against it."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    qg = q.reshape(b, t, n_kv, h // n_kv, d).astype(jnp.float32)
    s = jnp.einsum("btkgd,bjkd->bkgtj", qg, k.astype(jnp.float32),
                   precision=_HIGHEST)
    cum = jnp.cumsum(log_g.astype(jnp.float32), axis=1)     # [B,T,Hkv]
    ct = cum.transpose(0, 2, 1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    decay = jnp.exp(jnp.where(causal, ct[..., :, None] - ct[..., None, :],
                              -jnp.inf))                    # [B,Hkv,T,T]
    a = jnp.square(s) * decay[:, :, None]
    num = jnp.einsum("bkgtj,bjkd->btkgd", a, v.astype(jnp.float32),
                     precision=_HIGHEST)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + eps)).reshape(b, t, h, d).astype(q.dtype)


def _chunk_head(qg, k, v, lg, valid, s0, z0, before, eps):
    """One KV head of one chunk. ``qg`` [B, C, G, d], ``k``/``v``
    [B, C, d], ``lg`` [B, C] float32, ``valid`` [B, C] bool, ``s0``
    [B, rows, d], ``z0`` [B, d, d]. A row that is not valid adds
    nothing to the state: its key is masked and its gate is 1.
    ``before`` is what the positions before the chunk give each query
    at the chunk's start, ``(num [B, C, G, d], den [B, C, G])``, where
    :func:`_history_read` has read it from their keys and values;
    ``None`` reads it from the state."""
    c = k.shape[1]
    km = jnp.where(valid[..., None], k, jnp.zeros_like(k))
    kf, vf, qf = (x.astype(jnp.float32) for x in (km, v, qg))
    cum = jnp.cumsum(jnp.where(valid, lg, 0.0), axis=1)     # [B, C]
    # products of the operands as they are (bf16 ones are exact in
    # one pass), accumulated in float32
    s = jnp.einsum("btgd,bjd->bgtj", qg, km, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(causal, cum[:, :, None] - cum[:, None, :],
                              -jnp.inf))                    # [B, C, C]
    a = jnp.square(s) * decay[:, None]
    carry = jnp.exp(cum)                                    # [B, C]
    if before is None:
        before = (jnp.einsum("btgr,brd->btgd", phi_read(qg), s0,
                             precision=_HIGHEST),
                  jnp.einsum("btgi,bij,btgj->btg", qf, z0, qf,
                             precision=_HIGHEST))
    num = jnp.einsum("bgtj,bjd->btgd", a, vf, precision=_HIGHEST)
    num = num + carry[..., None, None] * before[0]
    den = (jnp.sum(a, axis=-1).transpose(0, 2, 1)
           + carry[..., None] * before[1])                  # [B, C, G]
    y = num / (den[..., None] + eps)
    tail = jnp.exp(cum[:, -1:] - cum)                       # [B, C]
    kw = kf * tail[..., None]
    s1 = carry[:, -1, None, None] * s0 + jnp.einsum(
        "bjr,bjd->brd", phi_write(km) * tail[..., None], vf,
        precision=_HIGHEST)
    z1 = carry[:, -1, None, None] * z0 + jnp.einsum(
        "bji,bjl->bil", kw, kf, precision=_HIGHEST)
    return y, s1, z1


def zero_history(batch: int, length: int, n_kv: int, d: int, dtype):
    """An empty prompt history for :func:`retention_chunk`: keys and
    values ``[B, length, Hkv, d]`` in ``dtype`` and each position's
    cumulative log-gate ``[B, length, Hkv]`` float32."""
    # two arrays, not one twice: a program may donate both
    return (jnp.zeros((batch, length, n_kv, d), dtype),
            jnp.zeros((batch, length, n_kv, d), dtype),
            jnp.zeros((batch, length, n_kv), jnp.float32))


def _history_read(qg, history, start, c: int):
    """What positions ``0 .. start - 1`` give the chunk's queries at
    the chunk's start, from their keys, values and cumulative
    log-gates, a block of ``c`` positions at a time in the attention
    form (``start`` is a multiple of ``c``; the loop is as long as the
    prompt so far). ``qg`` [B, C, Hkv, G, d]. Returns ``(num [Hkv, B,
    C, G, d], den [Hkv, B, C, G])``, laid out for the walk over
    heads."""
    k_h, v_h, cum_h = history
    b, rows, n_kv, groups, d = qg.shape
    # the gates between a position and the chunk's start
    total = jax.lax.dynamic_index_in_dim(
        cum_h, jnp.maximum(start - 1, 0), axis=1, keepdims=False)

    def block(i, acc):
        num, den = acc
        kb, vb, cb = (jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
                      for x in (k_h, v_h, cum_h))
        s = jnp.einsum("btkgd,bjkd->kbtgj", qg, kb, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        w = jnp.exp(total[:, None] - cb).transpose(2, 0, 1)  # [Hkv,B,c]
        a = jnp.square(s) * w[:, :, None, None, :]
        return (num + jnp.einsum("kbtgj,bjkd->kbtgd", a,
                                 vb.astype(jnp.float32),
                                 precision=_HIGHEST),
                den + jnp.sum(a, axis=-1))

    return jax.lax.fori_loop(
        0, start // c, block,
        (jnp.zeros((n_kv, b, rows, groups, d), jnp.float32),
         jnp.zeros((n_kv, b, rows, groups), jnp.float32)))


def retention_chunk(q, k, v, log_g, valid, state,
                    eps: float = RETENTION_EPS, history=None, start=0):
    """One chunk of C positions: inside it the attention form, before
    it the state. ``q`` [B, C, H, d], ``k``/``v`` [B, C, Hkv, d],
    ``log_g`` [B, C, Hkv], ``valid`` [B, C] bool (padded rows add
    nothing, and their outputs mean nothing), ``state`` as
    :func:`zero_state` gives it. Returns ``(y [B, C, H, d], state
    after the chunk's last valid row)``.

    A prompt that runs as several chunks may bring its ``history``
    (:func:`zero_history`; this chunk begins at position ``start``, a
    multiple of C): the chunk's queries then read what came before
    from the earlier chunks' keys and values, blockwise in the
    attention form, and not from ``state``, whose read expands every
    query to the state's rows (``phi_read``: 35 KB a query head and
    position at d = 128, where the attention form over a few thousand
    positions moves a fraction of that). The state is carried and
    written either way. Returns ``(y, state, history with this
    chunk's rows added)`` then."""
    b, c, h, d = q.shape
    n_kv = k.shape[2]
    qg = q.reshape(b, c, n_kv, h // n_kv, d)
    lg = log_g.astype(jnp.float32)
    per_head = [qg.transpose(2, 0, 1, 3, 4), k.transpose(2, 0, 1, 3),
                v.transpose(2, 0, 1, 3), lg.transpose(2, 0, 1),
                state[0].transpose(1, 0, 2, 3),
                state[1].transpose(1, 0, 2, 3)]
    if history is not None:
        per_head.append(_history_read(qg, history, start, c))

    def one(args):
        qh, kh, vh, lgh, s0, z0, *before = args
        return _chunk_head(qh, kh, vh, lgh, valid, s0, z0,
                           before[0] if before else None, eps)

    y, s1, z1 = jax.vmap(one)(tuple(per_head))
    y = y.transpose(1, 2, 0, 3, 4).reshape(b, c, h, d).astype(q.dtype)
    state = (s1.transpose(1, 0, 2, 3), z1.transpose(1, 0, 2, 3))
    if history is None:
        return y, state
    k_h, v_h, cum_h = history
    total = jnp.where(start > 0, jax.lax.dynamic_index_in_dim(
        cum_h, jnp.maximum(start - 1, 0), axis=1, keepdims=False), 0.0)
    cum = total[:, None] + jnp.cumsum(
        jnp.where(valid[..., None], lg, 0.0), axis=1)
    put = jax.lax.dynamic_update_slice_in_dim
    return y, state, (put(k_h, k.astype(k_h.dtype), start, axis=1),
                      put(v_h, v.astype(v_h.dtype), start, axis=1),
                      put(cum_h, cum, start, axis=1))


def retention_sequence(q, k, v, log_g, chunk: int, valid=None,
                       eps: float = RETENTION_EPS):
    """A whole sequence by the chunked form: a scan over chunks of
    ``chunk`` positions carrying the state from zero. Differentiable
    by autodiff (the training forward). Returns ``(y, final state)``."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    c = min(int(chunk), t)
    n = -(-t // c)
    pad = n * c - t
    if valid is None:
        valid = jnp.ones((b, t), bool)
    if pad:
        widths = ((0, 0), (0, pad))
        q, k, v = (jnp.pad(x, widths + ((0, 0), (0, 0)))
                   for x in (q, k, v))
        log_g = jnp.pad(log_g, widths + ((0, 0),))
        valid = jnp.pad(valid, widths)

    def split(x):
        return x.reshape(b, n, c, *x.shape[2:]).swapaxes(0, 1)

    def body(state, xs):
        y, state = retention_chunk(*xs, state, eps)
        return state, y

    state, ys = jax.lax.scan(body, zero_state(b, n_kv, d),
                             tuple(split(x) for x in
                                   (q, k, v, log_g, valid)))
    y = ys.swapaxes(0, 1).reshape(b, n * c, h, d)
    return y[:, :t], state


def retention_step(q, k, v, log_g, state, eps: float = RETENTION_EPS):
    """One position by the recurrence: ``q`` [B, H, d], ``k``/``v``
    [B, Hkv, d], ``log_g`` [B, Hkv]. Returns ``(y [B, H, d], state)``."""
    b, h, d = q.shape
    n_kv = k.shape[1]
    s0, z0 = state
    g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    qf = q.reshape(b, n_kv, h // n_kv, d).astype(jnp.float32)
    s1 = g * s0 + phi_write(kf)[..., None] * vf[..., None, :]
    z1 = g * z0 + kf[..., :, None] * kf[..., None, :]
    num = jnp.einsum("bkgr,bkrd->bkgd", phi_read(qf), s1,
                     precision=_HIGHEST)
    den = jnp.einsum("bkgi,bkij,bkgj->bkg", qf, z1, qf,
                     precision=_HIGHEST)
    y = num / (den[..., None] + eps)
    return y.reshape(b, h, d).astype(q.dtype), (s1, z1)
