"""Plain reference for the dense decoder cells: three COVAP training steps.

It imports nothing of the program under test.  From the configuration's
sizes it builds its own parameter tree (the layout the program's model
consumes, so the same seeded weights can be handed to both), its own
forward pass, loss and gradients in float32 at the highest matmul
precision, its own COVAP bucket plan, coarse filter and error feedback, and
its own AdamW with cosine warm-up.  It follows the decoder as this
repository defines it (PERF.md lists where that departs from the published
models): token embedding scaled by sqrt(d_model), RMS norms with a
``1 + scale`` gain, rotary attention over the full causal window, a gated
MLP, a final RMS norm and an untied head over the padded vocabulary.

Gradients are accumulated over blocks of rows and each layer is
recomputed in the backward pass, so that the reference fits on one chip
beside nothing else.

``matmul_dtype="float8"`` gives the control: the same reference with every
matmul operand rounded to float8 (e4m3 forward, e5m2 backward, each tensor
scaled to its own absolute maximum), the precision below the bfloat16
compute the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.correctness import leaf_norms

HIGHEST = lax.Precision.HIGHEST
F8_FWD, F8_BWD = jnp.float8_e4m3fn, jnp.float8_e5m2
# the paper's bucket: PyTorch DDP's 25 MiB, at most 128 buckets
BUCKET_BYTES = 25 * 1024 * 1024
MAX_BUCKETS = 128
# COVAP's compensation scheduler (paper SS III.D): the residual's weight
# starts at 0.3 and rises by 0.1 every 200 steps, to at most 1
EF_INIT, EF_EVERY, EF_RISE = 0.3, 200, 0.1


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(arch: dict) -> dict:
    """Leaf shapes of the parameter tree, stacked over layers."""
    L, d, f = arch["num_layers"], arch["d_model"], arch["d_ff"]
    H, K, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    V = padded_vocab(arch["vocab_size"])
    attn = {"wq": (L, d, H * hd), "wk": (L, d, K * hd),
            "wv": (L, d, K * hd), "wo": (L, H * hd, d)}
    if arch["qkv_bias"]:
        attn.update(bq=(L, H * hd), bk=(L, K * hd), bv=(L, K * hd))
    block = {"ln1": {"scale": (L, d)}, "attn": attn, "ln2": {"scale": (L, d)},
             "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                     "w_down": (L, f, d)}}
    return {"embed": {"table": (V, d)},
            "stack": {"blocks": {"b0": block},
                      "final_norm": {"scale": (d,)}},
            "head": {"w": (d, V)}}


def _leaf_init(key, path: str, shape):
    """Norm gains and biases start at zero, the table at N(0, 0.02), every
    matrix at N(0, 1/fan_in) truncated at two deviations."""
    name = path.rsplit("'", 2)[-2]
    if name in ("scale", "bq", "bk", "bv"):
        return jnp.zeros(shape, jnp.float32)
    if name == "table":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    std = 1.0 / math.sqrt(shape[-2])
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def init_params(arch: dict, seed: int, out_shardings=None):
    """The seeded float32 weights, made on the device in one jitted call."""
    shapes = param_shapes(arch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in flat]

    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf_init(k, p, s) for k, p, (_, s) in zip(keys, paths, flat)])

    fn = jax.jit(make, out_shardings=out_shardings)
    return fn(jax.random.key(np.uint32(seed % 2**32)))


# ---------------------------------------------------------------------------
# COVAP bucket plan (paper SS III.A and III.C, with the DDP 25 MiB bucket)
# ---------------------------------------------------------------------------

def bucket_ids(shapes: list[tuple], *, bucket_bytes: int, max_buckets: int,
               interval: int, itemsize: int = 4,
               shard_threshold: float = 2.0) -> list[np.ndarray]:
    """Per leaf, the bucket index of each row (``(rows,)``), or of each row
    and column (``(rows, shape[1])``) where a one-row bucket was split.

    Rows (slices along axis 0) are packed greedily into buckets of the
    target size, in leaf order, never splitting a row; then every bucket at
    least ``shard_threshold`` times the median is cut into
    ``min(numel // median, interval)`` even pieces, by rows, or along axis
    1 when the bucket is a single row."""
    rows = [s[0] if s else 1 for s in shapes]
    row_n = [int(np.prod(s[1:])) if len(s) > 1 else 1 for s in shapes]
    total = sum(r * n for r, n in zip(rows, row_n)) * itemsize
    target = max(bucket_bytes, math.ceil(total / max_buckets))

    raw, cur, cur_bytes = [], [], 0          # buckets as [(leaf, lo, hi)]
    for li in range(len(shapes)):
        rb = row_n[li] * itemsize
        if rb >= target:
            if cur:
                raw.append(cur)
                cur, cur_bytes = [], 0
            raw.extend([[(li, r, r + 1)] for r in range(rows[li])])
            continue
        r = 0
        while r < rows[li]:
            take = max(1, min(rows[li] - r, (target - cur_bytes) // rb))
            cur.append((li, r, r + take))
            cur_bytes += take * rb
            r += take
            if cur_bytes + rb > target:
                raw.append(cur)
                cur, cur_bytes = [], 0
    if cur:
        raw.append(cur)

    numels = [sum((hi - lo) * row_n[li] for li, lo, hi in b) for b in raw]
    median = int(np.median(numels))
    ids = [np.zeros(r, np.int64) for r in rows]
    nxt = 0
    for segs, numel in zip(raw, numels):
        parts = 1
        if numel >= shard_threshold * median:
            parts = max(int(min(numel // median, interval)), 1)
        li0, lo0, hi0 = segs[0]
        shape = shapes[li0]
        one_row = len(segs) == 1 and hi0 - lo0 == 1
        if parts > 1 and one_row:
            ax = next((a for a in range(1, len(shape)) if shape[a] > 1), None)
            if ax == 1:
                cols = shape[1]
                if ids[li0].ndim == 1:
                    ids[li0] = np.repeat(ids[li0][:, None], cols, axis=1)
                bounds = np.linspace(0, cols, min(parts, cols) + 1,
                                     dtype=np.int64)
                for a, b in zip(bounds[:-1], bounds[1:]):
                    if b > a:
                        ids[li0][lo0, a:b] = nxt
                        nxt += 1
                continue
            if ax is not None:
                raise NotImplementedError("split along an axis other than 1")
            parts = 1
        if parts == 1:
            for li, lo, hi in segs:
                ids[li][lo:hi] = nxt
            nxt += 1
            continue
        flat_rows = [(li, r) for li, lo, hi in segs for r in range(lo, hi)]
        bounds = np.linspace(0, len(flat_rows), min(parts, len(flat_rows)) + 1,
                             dtype=np.int64)
        for a, b in zip(bounds[:-1], bounds[1:]):
            if b > a:
                for li, r in flat_rows[a:b]:
                    ids[li][r] = nxt
                nxt += 1
    return ids


# ---------------------------------------------------------------------------
# forward, loss, gradients
# ---------------------------------------------------------------------------

def _scaled_round(x, dtype):
    s = lax.stop_gradient(
        float(jnp.finfo(dtype).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8(x):
    return _scaled_round(x, F8_FWD)


_fp8.defvjp(lambda x: (_scaled_round(x, F8_FWD), None),
            lambda _, ct: (_scaled_round(ct, F8_BWD),))


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(scale, x, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    S, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(arch, fp8, x, p):
    B, S, d = x.shape
    H, K, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    eps = arch["norm_eps"]
    h = _rmsnorm(p["ln1"]["scale"], x, eps)
    a = p["attn"]
    q = _mm("bsd,dh->bsh", h, a["wq"], fp8)
    k = _mm("bsd,dh->bsh", h, a["wk"], fp8)
    v = _mm("bsd,dh->bsh", h, a["wv"], fp8)
    if arch["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, H, hd), arch["rope_theta"])
    k = _rope(k.reshape(B, S, K, hd), arch["rope_theta"])
    v = v.reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)
    s = _mm("bqkgh,btkh->bkgqt", q, k, fp8) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bkgqt,btkh->bqkgh", jax.nn.softmax(s, axis=-1), v, fp8)
    x = x + _mm("bsh,hd->bsd", o.reshape(B, S, H * hd), a["wo"], fp8)
    h = _rmsnorm(p["ln2"]["scale"], x, eps)
    m = p["mlp"]
    g = _mm("bsd,df->bsf", h, m["w_gate"], fp8)
    act = (jax.nn.silu(g) if arch["mlp_act"] == "swiglu"
           else jax.nn.gelu(g, approximate=True))
    u = _mm("bsd,df->bsf", h, m["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", act * u, m["w_down"], fp8)


def loss_sum(arch, fp8, params, tokens, labels):
    """Summed token cross-entropy of a block of rows."""
    x = params["embed"]["table"][tokens] * math.sqrt(arch["d_model"])
    body = jax.checkpoint(lambda x, p: (_layer(arch, fp8, x, p), None))
    x, _ = lax.scan(body, x, params["stack"]["blocks"]["b0"])
    x = _rmsnorm(params["stack"]["final_norm"]["scale"], x, arch["norm_eps"])
    logits = _mm("bsd,dv->bsv", x, params["head"]["w"], fp8)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)


# ---------------------------------------------------------------------------
# the training steps
# ---------------------------------------------------------------------------

def _lr(train: dict, step):
    s = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(1.0, s / max(train["warmup_steps"], 1))
    span = max(train["total_steps"] - train["warmup_steps"], 1)
    frac = jnp.clip((s - train["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return train["lr"] * warm * cos


def _step(arch, train, fp8, block_rows, params, m, v, resid, ids,
          tokens, labels, step):
    """One COVAP step.  Returns the new state, the mean loss, the raw
    gradient and the gradient the optimizer received."""
    n_blocks = tokens.shape[0] // block_rows
    tb = tokens.reshape(n_blocks, block_rows, -1)
    lb = labels.reshape(n_blocks, block_rows, -1)
    grad_fn = jax.value_and_grad(partial(loss_sum, arch, fp8))

    def acc(carry, blk):
        total, g = carry
        l, gb = grad_fn(params, *blk)
        return (total + l, jax.tree.map(jnp.add, g, gb)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (total, g), _ = lax.scan(acc, (jnp.float32(0), zeros), (tb, lb))
    count = tokens.size
    g = jax.tree.map(lambda x: x / count, g)

    I = train["interval"]
    coeff = jnp.minimum(EF_INIT + jnp.floor(step / EF_EVERY) * EF_RISE, 1.0)

    def ef(gl, rl, idl):
        t = gl + coeff * rl
        sel = ((idl + step) % I == 0).reshape(
            idl.shape + (1,) * (gl.ndim - idl.ndim))
        return jnp.where(sel, t, 0.0), jnp.where(sel, 0.0, t)

    pairs = jax.tree.map(ef, g, resid, ids)
    synced = jax.tree.map(lambda pr: pr[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    resid = jax.tree.map(lambda pr: pr[1], pairs,
                         is_leaf=lambda x: isinstance(x, tuple))

    t = step + 1.0
    b1, b2, eps = train["b1"], train["b2"], train["eps"]
    lr = _lr(train, t)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, synced)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, synced)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / (1 - b1 ** t))
        / (jnp.sqrt(b / (1 - b2 ** t)) + eps), params, m, v)
    return params, m, v, resid, total / count, g, synced


def run(arch: dict, train: dict, seed: int, batches: list, *, steps: int = 3,
        matmul_dtype: str = "float32", block_rows: int = 1) -> dict:
    """Train ``steps`` steps from the seeded weights on ``batches`` (host
    arrays of the global batch).  ``train`` holds the COVAP interval and
    AdamW's ``lr``, ``warmup_steps``, ``total_steps``, ``b1``, ``b2`` and
    ``eps``.  Returns the loss of each step, the norm of each leaf of the
    raw first gradient and of the first gradient as the optimizer got it,
    of each leaf's change over the steps, and of each leaf of the
    error-feedback residual after the last step."""
    fp8 = {"float32": False, "float8": True}[matmul_dtype]
    with jax.default_device(jax.devices()[0]):
        params = init_params(arch, seed)
        shapes = [tuple(x.shape) for x in jax.tree.leaves(params)]
        id_list = bucket_ids(
            shapes, bucket_bytes=BUCKET_BYTES, max_buckets=MAX_BUCKETS,
            interval=train["interval"])
        ids = jax.tree_util.tree_unflatten(
            jax.tree.structure(params),
            [jnp.asarray(i, jnp.int32) for i in id_list])
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        m, v, resid = zeros(params), zeros(params), zeros(params)
        step_fn = jax.jit(partial(_step, arch, train, fp8, block_rows))
        start = params
        losses = []
        for s in range(steps):
            b = batches[s]
            params, m, v, resid, loss, g, synced = step_fn(
                params, m, v, resid, ids, jnp.asarray(b["tokens"]),
                jnp.asarray(b["labels"]), jnp.float32(s))
            losses.append(float(loss))
            if s == 0:
                raw, first = leaf_norms(g), leaf_norms(synced)
            del g, synced
        change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
        resid = leaf_norms(resid)
    return {"losses": losses, "raw_grad": raw, "grad1": first,
            "change": change, "resid": resid}
