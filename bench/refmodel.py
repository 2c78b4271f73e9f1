"""Plain reference of the PANTHER train step, in ``jax.numpy`` and float32.

It imports nothing of the program under test and takes nothing it made. The
model is the dense decoder of the repository's ``LMConfig`` (RMSNorm with a
``1 + scale`` gain, fused q/k/v projection, grouped-query causal attention
with rotary positions on the full head, SwiGLU MLP, tied or untied head,
mean next-token cross entropy). The optimizer is PANTHER's (paper section 3):

* a crossbar weight is held as ``S`` balanced base-16 digit planes of its
  32-bit fixed-point value on a per-tensor ``2^-F`` grid, ``F`` chosen from
  the tensor's largest magnitude with ``margin_bits`` of headroom;
* a step quantizes ``-lr * grad`` onto that grid with stochastic rounding,
  splits it into balanced digits and adds each digit to its plane, clipped
  to the plane's rail ``2^(bits-1)`` (carries stay in the plane's headroom;
  no carry resolution falls in the steps compared);
* vectors (norm gains) take plain SGD in float32.

In a cell with an analog read (``adc_bits``), every q/k/v, attention-output
and MLP projection reads its planes through the bit-sliced crossbar: the
input is DAC-quantized to ``io_bits`` on a power-of-two scale chosen from
the whole tensor's largest magnitude, streamed one magnitude bit per cycle,
each 128-row tile's column current per (bit, slice) passes a mid-tread ADC
of ``adc_bits`` over ``rows * plane_max`` full scale, and the codes are
shifted and added. The layer gradient reads the same planes from the
columns; the weight gradient is the outer product ``x^T dy``.

``mode`` picks the arithmetic of every matrix product outside the crossbar
reads: ``"f32"`` (HIGHEST precision, the reference) or ``"fp8"`` (both
operands and the cotangent rounded to float8 e4m3 under a per-tensor scale,
the precision below the program's bfloat16, used as the control).

A configuration file names its reference module under ``bench/`` by its
top-level ``"reference"`` (this one where it names none; ``bench/run.py``'s
``load_reference``). The harness (``bench/run.py``, ``bench/control.py``,
``bench/metrics/mfu.train.py`` and ``tests/bench/``) uses only this
interface of it, which every reference module provides:

* ``Model.from_config(config) -> Model``: the file's ``config`` object; a
  configuration the module does not model is an error. ``Model.vocab`` is
  the vocabulary the traffic draws ids from.
* ``seed_key(seed)``: the PRNG key of any whole number below 2**64.
* ``gen_params(key, m) -> {path: array}``: the initial weights, flat, in
  the program's parameter layout; ``nest(flat)`` makes the program's tree.
* ``Reader(m, num)``: ``reader(key, planes, digital) -> (norms, frac_bits)``
  per leaf, ``||w - w0||`` against the initial state of ``key``;
  ``reader.norm`` is keyed by the crossbar leaves, whose paths the program's
  planes must match.
* ``reference_readings(key, m, num, mode, lr, batches) -> dict``: the first
  steps from the initial state of ``key``, the readings ``bench.compare``
  takes, ``mode`` ``"f32"`` (the reference) or ``"fp8"`` (the control).
* ``describe(m) -> str``, and ``model_flops_per_token(m, seq)``: the model
  FLOPs a trained token costs, which ``mfu.train`` counts.

``Numerics`` (PANTHER's number formats) stays here: another reference
imports it, so that every cell holds its weights in the same formats.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn
LOSS_CHUNK = 512  # tokens per head chunk: bounds the [chunk, vocab] logits
QUERY_CHUNK = 512  # queries per attention block: bounds the score tensor
READ_COLS = 1024  # output columns per crossbar-read block
UPDATE_BLOCK = 1 << 23  # weights per block of rows (generator, update, readings)


@dataclasses.dataclass(frozen=True)
class Model:
    """The model sizes of a configuration file's ``config`` object."""

    d_model: int
    n_layers: int
    vocab: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    qk_norm: bool = False
    act: str = "silu"

    @classmethod
    def from_config(cls, config: dict) -> "Model":
        """The dense decoder of a ``config`` whose ``block`` is ``dense``, or
        whose ``pattern`` is one group of ``n_layers`` dense blocks. A key
        this model does not read (a block of another kind, ``mla``, ``moe``,
        a window, a soft cap) is an error: the reference would leave it out."""
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(config) - names - {"block", "pattern", "dtype"})
        if unknown:
            raise ValueError(f"the dense reference does not model {', '.join(unknown)}")
        pattern = [list(g) for g in config.get("pattern", [[config.get("block", "dense"), config["n_layers"]]])]
        if pattern != [["dense", config["n_layers"]]] or config.get("block", "dense") != "dense":
            raise ValueError(f"the dense reference models one group of {config['n_layers']} dense blocks, "
                             f"not block {config.get('block')!r}, pattern {pattern}")
        return cls(**{k: v for k, v in config.items() if k in names})


@dataclasses.dataclass(frozen=True)
class Numerics:
    """PANTHER's number formats. Every cell holds its weights in the paper's
    32-bit word, sliced 44466555, and reads through 16-bit DACs into
    128-row crossbars; a traffic file's ``fidelity`` object gives the ADC
    widths of a cell with an analog read."""

    slice_bits: ClassVar[tuple] = (4, 4, 4, 6, 6, 5, 5, 5)  # MSB first, paper notation
    weight_bits: ClassVar[int] = 32
    margin_bits: ClassVar[int] = 2
    io_bits: ClassVar[int] = 16
    dac_margin_bits: ClassVar[int] = 1
    xbar_rows: ClassVar[int] = 128
    adc_bits_fwd: int | None = None
    adc_bits_bwd: int | None = None

    @classmethod
    def from_traffic(cls, traffic: dict) -> "Numerics":
        fid = traffic.get("fidelity") or {}
        return cls(adc_bits_fwd=fid.get("adc_bits_fwd"), adc_bits_bwd=fid.get("adc_bits_bwd"))

    @property
    def n_slices(self) -> int:
        return len(self.slice_bits)

    @property
    def plane_max(self) -> tuple:
        """Rail of each plane, least significant first."""
        return tuple(1 << (b - 1) for b in reversed(self.slice_bits))

    @property
    def canonical_limit(self) -> int:
        return 7 * (16**self.n_slices - 1) // 15

    @property
    def analog(self) -> bool:
        return self.adc_bits_fwd is not None or self.adc_bits_bwd is not None


# ------------------------------ parameters ---------------------------------

MATRIX_KEYS = ("embed", "lm_head", "wqkv", "wo", "wi_gate", "wi_up")
READ_KEYS = ("attn/wqkv", "attn/wo", "mlp/wi_gate", "mlp/wi_up", "mlp/wo")


def param_specs(m: Model) -> dict:
    """``path -> (shape, init std or None for a norm gain)`` in the program's
    parameter layout: one layer group whose leaves carry a leading layer axis
    when it holds more than one layer."""
    d, H, KV, hd, ff = m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff
    st = (m.n_layers,) if m.n_layers > 1 else ()
    g = "groups/0/"
    specs = {
        "embed": ((m.vocab, d), 0.02),
        "final_ln/scale": ((d,), None),
        g + "attn/wqkv": (st + (d, (H + 2 * KV) * hd), d**-0.5),
        g + "attn/wo": (st + (H * hd, d), (H * hd) ** -0.5),
        g + "attn/ln/scale": (st + (d,), None),
        g + "mlp/wi_gate": (st + (d, ff), d**-0.5),
        g + "mlp/wi_up": (st + (d, ff), d**-0.5),
        g + "mlp/wo": (st + (ff, d), ff**-0.5),
        g + "mlp/ln/scale": (st + (d,), None),
    }
    if m.qk_norm:
        specs[g + "attn/qn/scale"] = (st + (hd,), None)
        specs[g + "attn/kn/scale"] = (st + (hd,), None)
    if not m.tie_embeddings:
        specs["lm_head"] = ((d, m.vocab), d**-0.5)
    return dict(sorted(specs.items()))


def is_matrix(path: str) -> bool:
    return path.rsplit("/", 1)[-1] in MATRIX_KEYS


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number below 2**64."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def row_blocks(shape: tuple) -> tuple:
    """A leaf seen as ``[rows, C]`` (``C`` its last axis) -> ``(rows, C, rb)``
    with ``rb`` the largest divisor of ``rows`` that keeps a block of rows
    within ``UPDATE_BLOCK`` weights."""
    C = shape[-1]
    rows = math.prod(shape[:-1])
    rb = max(d for d in range(1, min(rows, max(1, UPDATE_BLOCK // C)) + 1) if rows % d == 0)
    return rows, C, rb


def leaf_block(key, m: Model, path: str, b) -> jax.Array:
    """Rows ``b * rb .. (b + 1) * rb`` of a leaf of the initial weights, as
    ``[rb, C]``: normal with the leaf's std; norm gains draw ``0.1 * normal``
    so that their ``1 + scale`` matters. Each block has a key of its own, so
    a block can be made again without the rest of its leaf."""
    specs = param_specs(m)
    shape, std = specs[path]
    _, C, rb = row_blocks(shape)
    k = jax.random.fold_in(jax.random.fold_in(key, list(specs).index(path)), b)
    return jax.random.normal(k, (rb, C), jnp.float32) * (0.1 if std is None else std)


def gen_leaf(key, m: Model, path: str) -> jax.Array:
    """One leaf of the initial weights, block by block (``leaf_block``)."""
    shape, _ = param_specs(m)[path]
    rows, _, rb = row_blocks(shape)
    blocks = jax.vmap(lambda b: leaf_block(key, m, path, b))(jnp.arange(rows // rb))
    return blocks.reshape(shape)


def gen_params(key, m: Model) -> dict:
    return {p: gen_leaf(key, m, p) for p in param_specs(m)}


def nest(flat: dict) -> dict:
    """``{"a/0/b": x}`` -> ``{"a": [{"b": x}]}`` (numeric parts are list
    indices), the program's parameter tree."""
    root: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = root
        for i, part in enumerate(parts[:-1]):
            nxt = [] if parts[i + 1].isdigit() else {}
            if isinstance(node, list):
                idx = int(part)
                while len(node) <= idx:
                    node.append(None)
                if node[idx] is None:
                    node[idx] = nxt
                node = node[idx]
            else:
                node = node.setdefault(part, nxt)
        node[parts[-1]] = v
    return root


# ------------------------------ fixed point --------------------------------


def scale_exponent(x, word_bits: int, margin_bits: int, lo: int, hi: int) -> jax.Array:
    """``F`` such that ``max|x| * 2^F`` keeps ``margin_bits`` of headroom in a
    signed ``word_bits`` word: ``word_bits - 1 - margin - ceil(log2 max|x|)``,
    clipped to ``[lo, hi]``; ``max|x| = 0`` gives ``word_bits - 1 - margin``."""
    return exponent_of_max(jnp.max(jnp.abs(x.astype(jnp.float32))), word_bits, margin_bits, lo, hi)


def exponent_of_max(mx, word_bits: int, margin_bits: int, lo: int, hi: int) -> jax.Array:
    """``scale_exponent`` of a tensor whose largest magnitude is ``mx``."""
    mant, e = jnp.frexp(mx)  # mx = mant * 2^e, mant in [0.5, 1)
    ceil_log2 = jnp.where(mant > 0.5, e, e - 1)
    f = word_bits - 1 - margin_bits - ceil_log2
    f = jnp.where(mx == 0.0, word_bits - 1 - margin_bits, f)
    return jnp.clip(f, lo, hi).astype(jnp.int32)


def weight_exponent(w, num: Numerics) -> jax.Array:
    return scale_exponent(w, num.weight_bits, num.margin_bits, 0, num.weight_bits - 1)


def to_grid(x, f, bits: int, noise=None) -> jax.Array:
    """Round ``x * 2^f`` to an integer (nearest, or down after adding U[0,1)
    noise: stochastic rounding) and saturate to a signed ``bits`` word."""
    y = jnp.ldexp(x.astype(jnp.float32), f)
    y = jnp.round(y) if noise is None else jnp.floor(y + noise)
    lim = float(2 ** (bits - 1) - 1)
    return jnp.clip(y, -lim, lim).astype(jnp.int32)


def balanced_digits(q, num: Numerics) -> list:
    """Balanced base-16 digits in [-8, 7] of int32 ``q`` (clipped to the
    canonical range), least significant first."""
    rem = jnp.clip(q, -num.canonical_limit, num.canonical_limit)
    out = []
    for _ in range(num.n_slices):
        d = ((rem + 8) % 16) - 8
        out.append(d)
        rem = (rem - d) // 16
    return out


def planes_of(w, num: Numerics):
    """-> (int8 planes [S, *w.shape], F): the canonical crossbar state."""
    f = weight_exponent(w, num)
    q = to_grid(w, f, num.weight_bits)
    return jnp.stack(balanced_digits(q, num)).astype(jnp.int8), f


def planes_value(planes) -> jax.Array:
    """``sum_s plane_s 16^s`` in float32 (most significant plane first)."""
    acc = planes[-1].astype(jnp.float32)
    for s in range(planes.shape[0] - 2, -1, -1):
        acc = acc * 16.0 + planes[s].astype(jnp.float32)
    return acc


def deposit(planes, upd, num: Numerics):
    """Add the balanced digits of the int32 update to each plane, clipped to
    the plane's rail."""
    digits = balanced_digits(upd, num)
    return jnp.stack([
        jnp.clip(planes[s].astype(jnp.int32) + digits[s], -pm, pm)
        for s, pm in enumerate(num.plane_max)
    ]).astype(jnp.int8)


# ------------------------------ arithmetic ---------------------------------


def _f32_einsum(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def round_f8(x):
    """Round to float8 e4m3 under a per-tensor scale (amax -> 448)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _f8_einsum(spec: str):
    @jax.custom_vjp
    def f(a, b):
        return _f32_einsum(spec, round_f8(a), round_f8(b))

    def fwd(a, b):
        aq, bq = round_f8(a), round_f8(b)
        return _f32_einsum(spec, aq, bq), (aq, bq)

    def bwd(res, dy):
        _, vjp = jax.vjp(functools.partial(_f32_einsum, spec), *res)
        return vjp(round_f8(dy))

    f.defvjp(fwd, bwd)
    return f


def einsum(mode: str, spec: str, a, b):
    if mode == "f32":
        return _f32_einsum(spec, a, b)
    if mode == "fp8":
        return _f8_einsum(spec)(a, b)
    raise ValueError(f"unknown arithmetic {mode!r}")


def crossbar_read(planes, f, x, adc_bits: int, num: Numerics, transpose: bool = False):
    """Bit-sliced crossbar read of float ``x [..., K]`` through planes
    ``[S, M, N]`` on the ``2^-f`` grid (``K = M``; ``K = N`` when
    ``transpose``). Every column current is an integer below
    ``rows * max plane``, exact in a bfloat16 x bfloat16 -> float32 product."""
    S = planes.shape[0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    T = x2.shape[0]
    xf = scale_exponent(x2, num.io_bits, num.dac_margin_bits, -64, 64)
    xq = to_grid(x2, xf, num.io_bits)
    nb = num.io_bits - 1
    t = jnp.arange(nb, dtype=jnp.int32)[:, None, None]
    bits = (((jnp.abs(xq)[None] >> t) & 1) * jnp.sign(xq)[None]).astype(jnp.bfloat16)
    w = planes.astype(jnp.bfloat16)
    if transpose:
        w = jnp.swapaxes(w, 1, 2)
    K, N = w.shape[1:]
    R = num.xbar_rows
    if K % R:
        raise ValueError(f"contraction {K} is not a whole number of {R}-row tiles")
    nc = READ_COLS if N % READ_COLS == 0 else N
    pm = jnp.asarray(num.plane_max, jnp.float32)
    step = 2.0 * R * pm / 2.0**adc_bits  # [S]
    half = float(2 ** (adc_bits - 1))
    weights = (2.0 ** jnp.arange(nb, dtype=jnp.float32))[:, None] * (
        16.0 ** jnp.arange(S, dtype=jnp.float32) * step)[None, :]  # [nb, S]

    def col_block(j):
        def tile(acc, k):
            b = jax.lax.dynamic_slice_in_dim(bits, k * R, R, axis=2)
            wt = jax.lax.dynamic_slice(w, (0, k * R, j * nc), (S, R, nc))
            cur = jnp.einsum("tbr,srn->tbsn", b, wt, preferred_element_type=jnp.float32)
            code = jnp.clip(jnp.round(cur / step[:, None]), -half, half)
            return acc + jnp.einsum("tbsn,ts->bn", code, weights, precision=HIGHEST), None

        acc, _ = jax.lax.scan(tile, jnp.zeros((T, nc), jnp.float32), jnp.arange(K // R))
        return acc

    out = jax.lax.map(col_block, jnp.arange(N // nc))  # [N / nc, T, nc]
    out = jnp.moveaxis(out, 0, 1).reshape(T, N)
    return jnp.ldexp(out, -(xf + f)).reshape(*lead, N)


@functools.lru_cache(maxsize=None)
def _analog_linear(num: Numerics, mode: str):
    """``x @ w`` read through the crossbar: forward MVM at ``adc_bits_fwd``,
    ``dx`` by the transposed read at ``adc_bits_bwd``, ``dw = x^T dy``."""

    def read(planes, f, v, transpose):
        bits = num.adc_bits_bwd if transpose else num.adc_bits_fwd
        if bits is None:
            raise NotImplementedError("an ideal-ADC read is not part of any cell")
        return crossbar_read(planes, f, v, bits, num, transpose)

    @jax.custom_vjp
    def lin(x, w, planes, f):
        return read(planes, f, x, False)

    def fwd(x, w, planes, f):
        return read(planes, f, x, False), (x, planes, f)

    def bwd(res, dy):
        x, planes, f = res
        dx = read(planes, f, dy, True)
        x2 = x.reshape(-1, x.shape[-1])
        dy2 = dy.reshape(-1, dy.shape[-1])
        dw = einsum(mode, "tm,tn->mn", x2, dy2)
        return dx, dw, None, None

    lin.defvjp(fwd, bwd)
    return lin


# -------------------------------- model ------------------------------------


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, theta):
    """Rotary positions over the whole head: x [B, S, heads, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, mode):
    """Causal grouped-query attention; q [B,S,H,hd], k/v [B,S,KV,hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qc = min(S, QUERY_CHUNK)
    qb = jnp.moveaxis(q.reshape(B, S // qc, qc, KV, g, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = einsum(mode, "bqkgh,bskh->bkgqs", qi, k) * (hd**-0.5)
        ok = jnp.arange(S)[None, :] <= (i * qc + jnp.arange(qc))[:, None]
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return einsum(mode, "bkgqs,bskh->bqkgh", p, v)

    out = jax.lax.map(block, (jnp.arange(S // qc), qb))  # [nq, B, qc, KV, g, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def loss_fn(w, planes, fb, ids, labels, m: Model, num: Numerics, mode: str):
    """Mean next-token cross entropy of the flat weights ``w``; ``planes``
    and ``fb`` feed the crossbar reads of an analog cell."""
    lin_analog = _analog_linear(num, mode) if num.analog else None
    g = "groups/0/"

    def layer_w(path, l):
        a = w[g + path]
        return a[l] if m.n_layers > 1 else a

    def linear(x, path, l):
        if lin_analog is None:
            return einsum(mode, "bsm,mn->bsn", x, layer_w(path, l))
        p = planes[g + path]
        return lin_analog(x, layer_w(path, l), p[:, l] if m.n_layers > 1 else p, fb[g + path])

    def layer(h, l):
        B, S, _ = h.shape
        H, KV, hd = m.n_heads, m.n_kv_heads, m.head_dim
        x = rms_norm(h, layer_w("attn/ln/scale", l), m.norm_eps)
        qkv = linear(x, "attn/wqkv", l)
        q, k, v = jnp.split(qkv, [H * hd, (H + KV) * hd], axis=-1)
        q, k, v = q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd)
        if m.qk_norm:
            q = rms_norm(q, layer_w("attn/qn/scale", l), m.norm_eps)
            k = rms_norm(k, layer_w("attn/kn/scale", l), m.norm_eps)
        q, k = rope(q, m.rope_theta), rope(k, m.rope_theta)
        h = h + linear(attention(q, k, v, mode), "attn/wo", l)
        x = rms_norm(h, layer_w("mlp/ln/scale", l), m.norm_eps)
        gate = linear(x, "mlp/wi_gate", l)
        act = jax.nn.silu(gate) if m.act == "silu" else jax.nn.gelu(gate)
        return h + linear(act * linear(x, "mlp/wi_up", l), "mlp/wo", l)

    h = w["embed"][ids]
    for l in range(m.n_layers):
        h = jax.checkpoint(functools.partial(layer, l=l))(h)
    h = rms_norm(h, w["final_ln/scale"], m.norm_eps)
    T = ids.size
    h = h.reshape(T, -1)
    lab = labels.reshape(T)
    head, spec = (w["embed"], "tm,vm->tv") if m.tie_embeddings else (w["lm_head"], "tm,mv->tv")
    c = min(T, LOSS_CHUNK)

    @jax.checkpoint
    def chunk(acc, xs):
        hc, lc = xs
        logits = einsum(mode, spec, hc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(lse - ll), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                            (h.reshape(T // c, c, -1), lab.reshape(T // c, c)))
    return total / T


# ------------------------------ the step -----------------------------------


def init_state(key, m: Model, num: Numerics):
    """-> (planes, frac_bits, digital): flat dicts of the initial state."""
    planes, fb, digital = {}, {}, {}
    for path in param_specs(m):
        w = gen_leaf(key, m, path)
        if is_matrix(path):
            planes[path], fb[path] = planes_of(w, num)
        else:
            digital[path] = w
    return planes, fb, digital


def grads(w, digital, planes, fb, ids, labels, m: Model, num: Numerics, mode: str):
    """-> (loss, gradients of the crossbar weights ``w``, of the vectors)."""
    def f(w, digital):
        return loss_fn({**w, **digital}, planes, fb, ids, labels, m, num, mode)

    loss, (gw, gd) = jax.value_and_grad(f, argnums=(0, 1))(w, digital)
    return loss, gw, gd


def update_leaf(planes, g, f, key, lr, num: Numerics):
    """PANTHER's update of one crossbar leaf, a block of rows at a time.
    ``planes`` [S, *shape] and the leaf's gradient ``g`` [*shape] are
    updated in place (the caller donates both): each block's planes take the
    deposit, and ``g``'s rows take the new dequantized weights.
    -> (planes', w)."""
    R, C, rb = row_blocks(g.shape)

    def block(b, carry):
        planes, w = carry
        r0 = b * rb
        pb = jax.lax.dynamic_slice_in_dim(planes, r0, rb, axis=1)
        gb = jax.lax.dynamic_slice_in_dim(w, r0, rb, axis=0)
        noise = jax.random.uniform(jax.random.fold_in(key, b), gb.shape, jnp.float32)
        new = deposit(pb, to_grid(-lr * gb, f, num.weight_bits, noise), num)
        planes = jax.lax.dynamic_update_slice_in_dim(planes, new, r0, axis=1)
        w = jax.lax.dynamic_update_slice_in_dim(w, dequantize(new, f), r0, axis=0)
        return planes, w

    carry = (planes.reshape(planes.shape[0], R, C), g.reshape(R, C))
    new_planes, w = jax.lax.fori_loop(0, R // rb, block, carry)
    return new_planes.reshape(planes.shape), w.reshape(g.shape)


def dequantize(planes, f):
    return jnp.ldexp(planes_value(planes), -f)


class Reference:
    """The reference's state and steps, all on the device: the digit planes
    (8 bytes a weight), the float32 weights they hold, and the vectors. A
    step's gradient takes the weights' buffers, and the update writes the
    new planes and weights into the planes' and the gradient's buffers."""

    def __init__(self, key, m: Model, num: Numerics, mode: str, lr: float):
        self.key, self.m, self.num, self.lr = key, m, num, lr
        self.planes, self.fb, self.digital = jax.jit(functools.partial(init_state, m=m, num=num))(key)
        jax.clear_caches()  # a loaded program keeps its temporaries reserved
        deq = jax.jit(dequantize)
        self.w = {p: deq(a, self.fb[p]) for p, a in self.planes.items()}
        self.reader = Reader(m, num)
        self._grads = jax.jit(functools.partial(grads, m=m, num=num, mode=mode), donate_argnums=0)
        self._update = jax.jit(functools.partial(update_leaf, num=num), donate_argnums=(0, 1))
        self.keys = jax.random.fold_in(key, 2)
        self.steps = 0

    def step(self, ids, labels, read: bool = False):
        """One step -> (loss, {leaf: ||w - w0||} when ``read``, else None)."""
        reads = self.planes if self.num.analog else {}
        loss, g, gd = self._grads(self.w, self.digital, reads, self.fb, ids, labels)
        key = jax.random.fold_in(self.keys, self.steps)
        for i, p in enumerate(sorted(self.planes)):
            self.planes[p], self.w[p] = self._update(self.planes.pop(p), g.pop(p), self.fb[p],
                                                     jax.random.fold_in(key, i), self.lr)
        self.digital = {p: d - self.lr * gd[p] for p, d in self.digital.items()}
        self.steps += 1
        return float(loss), (self.reader(self.key, self.planes, self.digital)[0] if read else None)


def change_norm(planes, key, m: Model, num: Numerics, path: str):
    """-> (||w - w0||, F0) of one crossbar leaf: ``w0`` is the canonical
    initial state made from ``key``. The leaf is read block of rows by block
    of rows, ``w0``'s blocks made again in place, so that the reading needs
    little device memory beside the state it reads; the difference is summed
    digit by digit, so it is exact while it stays below 2^24 grid steps."""
    rows, C, rb = row_blocks(param_specs(m)[path][0])
    p = planes.reshape(num.n_slices, rows, C)

    def block_max(b, mx):
        return jnp.maximum(mx, jnp.max(jnp.abs(leaf_block(key, m, path, b))))

    mx = jax.lax.fori_loop(0, rows // rb, block_max, jnp.zeros((), jnp.float32))
    f0 = exponent_of_max(mx, num.weight_bits, num.margin_bits, 0, num.weight_bits - 1)

    def block_sq(b, acc):
        d0 = balanced_digits(to_grid(leaf_block(key, m, path, b), f0, num.weight_bits), num)
        pb = jax.lax.dynamic_slice_in_dim(p, b * rb, rb, axis=1)
        d = jnp.zeros((rb, C), jnp.float32)
        for s in range(num.n_slices - 1, -1, -1):
            d = d * 16.0 + (pb[s].astype(jnp.int32) - d0[s]).astype(jnp.float32)
        return acc + jnp.sum(d * d)

    sq = jax.lax.fori_loop(0, rows // rb, block_sq, jnp.zeros((), jnp.float32))
    return jnp.ldexp(jnp.sqrt(sq), -f0), f0


def digital_change_norms(digital, key, m: Model) -> dict:
    return {p: jnp.linalg.norm((d - gen_leaf(key, m, p)).ravel()) for p, d in digital.items()}


class Reader:
    """The readings of a state against the initial state of ``key``: per
    leaf ``||w - w0||``, and per crossbar leaf the exponent of ``w0``'s grid.
    One compiled function per crossbar leaf bounds the temporaries."""

    def __init__(self, m: Model, num: Numerics):
        self.norm = {p: jax.jit(functools.partial(change_norm, m=m, num=num, path=p))
                     for p in param_specs(m) if is_matrix(p)}
        self.digital = jax.jit(functools.partial(digital_change_norms, m=m))

    def __call__(self, key, planes: dict, digital: dict) -> tuple:
        norms, fbits = {}, {}
        for p, fn in self.norm.items():
            n, f = fn(planes[p], key)
            norms[p], fbits[p] = float(n), int(f)
        norms.update({p: float(v) for p, v in self.digital(digital, key).items()})
        return norms, fbits


def reference_readings(key, m: Model, num: Numerics, mode: str, lr: float, batches) -> dict:
    """Run the reference through ``batches`` (``[(ids, labels)]``) from the
    initial state of ``key`` -> the readings ``bench.compare`` takes."""
    ref = Reference(key, m, num, mode, lr)
    out = {"loss": [], "frac_bits": {p: int(f) for p, f in ref.fb.items()}}
    for i, (x, y) in enumerate(batches):
        loss, norms = ref.step(x, y, read=i in (0, len(batches) - 1))
        out["loss"].append(loss)
        if norms is not None:
            out.setdefault("change1", norms)
            out["change"] = norms
    return out


def model_flops_per_token(m: Model, seq: int) -> float:
    """Model FLOPs of one trained token: 6x the parameters the forward
    multiplies by (q/k/v, attention output and MLP projections, and the
    output head once; the embedding gather counts nothing) plus 3x the causal
    forward attention FLOPs. Recomputation does not count."""
    per_layer = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim \
        + m.n_heads * m.head_dim * m.d_model + 3 * m.d_model * m.d_ff
    matmul_params = m.n_layers * per_layer + m.vocab * m.d_model
    # QK^T and PV: 2 FLOPs x head_dim x heads per (query, key) pair, and a
    # query at position i sees i + 1 keys: (seq + 1) / 2 on average
    attn_fwd = m.n_layers * 2 * 2 * m.n_heads * m.head_dim * (seq + 1) / 2
    return 6.0 * matmul_params + 3.0 * attn_fwd


def n_params(m: Model) -> int:
    return sum(math.prod(s) for s, _ in param_specs(m).values())


def describe(m: Model) -> str:
    return (f"d_model {m.d_model}, heads {m.n_heads}/{m.n_kv_heads} x {m.head_dim}, d_ff {m.d_ff}, "
            f"layers {m.n_layers}, vocab {m.vocab}, {'tied' if m.tie_embeddings else 'untied'}, "
            f"{n_params(m) / 1e6:.1f}M parameters")
