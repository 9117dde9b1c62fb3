"""Plain reference of a dense decoder (Mistral, InternLM2: pre-norm
RMSNorm, rotary grouped-query attention, SwiGLU, untied head).

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no batching tricks. It
imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``chipbench.weights``, one layer
at a time, so that it fits beside nothing else on one chip.

Departures from the published models, each because the program has no
knob for it and the reference has to state the same function:
``rms_norm_eps`` is the configuration file's (1e-6, the program's
constant; the sources say 1e-5); InternLM2's fused ``wqkv`` is three
tensors (the same mathematics); its dynamic-NTK ``rope_scaling`` is left
out (it engages only past 32,768 positions).

``lowp`` is the control of "How ``correct`` is decided": the same
function with every matmul operand first rounded to 8-bit floating point
(e4m3, scaled by the row's largest magnitude), the precision below the
bfloat16 the configurations state. The benchmark's runs never use it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _lowp(x: jax.Array) -> jax.Array:
    """Round to e4m3 with one scale a row (last axis). The rounding is
    of the values only: gradients pass straight through it, as they do
    where 8-bit matmuls are trained (an unscaled 8-bit cotangent would
    underflow to nothing)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    rounded = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(eq: str, a: jax.Array, b: jax.Array, lowp: bool) -> jax.Array:
    if lowp:
        a, b = _lowp(a), _lowp(b)
    return jnp.einsum(eq, a, b, precision=HI)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding in the split-half layout. x: [B, S, H, E]."""
    e = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_forward(
    x: jax.Array, w: dict, dims: W.Dims, lowp: bool = False
) -> jax.Array:
    """One decoder layer on [B, S, D] float32; ``w`` holds float32
    ``ln1 ln2 wq wk wv wo w_gate w_up w_down`` of this layer."""
    b, s, _ = x.shape
    h = rms_norm(x, w["ln1"], dims.rms_eps)
    q = rope(_mm("bsd,dhe->bshe", h, w["wq"], lowp), dims.rope_theta)
    k = rope(_mm("bsd,dke->bske", h, w["wk"], lowp), dims.rope_theta)
    v = _mm("bsd,dke->bske", h, w["wv"], lowp)
    rep = dims.heads // dims.kv_heads
    q = q.reshape(b, s, dims.kv_heads, rep, dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for row in range(b):  # a row at a time: [K, R, S, S] scores stay small
        sc = _mm("skre,tke->krst", q[row], k[row], lowp)
        sc = sc / math.sqrt(dims.head_dim)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(_mm("krst,tke->skre", p, v[row], lowp))
    attn = jnp.stack(outs).reshape(b, s, dims.heads, dims.head_dim)
    x = x + _mm("bshe,hed->bsd", attn, w["wo"], lowp)
    h = rms_norm(x, w["ln2"], dims.rms_eps)
    gate = jax.nn.silu(_mm("bsd,df->bsf", h, w["w_gate"], lowp))
    up = _mm("bsd,df->bsf", h, w["w_up"], lowp)
    return x + _mm("bsf,fd->bsd", gate * up, w["w_down"], lowp)


def serving_layer_weights(key: jax.Array, dims: W.Dims, layer) -> dict:
    """Layer ``layer`` of the served int8 model, dequantised to float32."""
    w = {
        name: W.draw_int8(key, dims, name, layer).astype(jnp.float32)
        * W.int8_scale(dims, name)
        for name in W.LAYER_TENSORS
    }
    w["ln1"] = w["ln2"] = jnp.ones((dims.hidden,), jnp.float32)
    return w


def training_layer_weights(key: jax.Array, dims: W.Dims, layer, dtype) -> dict:
    """Layer ``layer`` as the trained model stores it at step 0, in
    float32 (the stored values, rounded to ``dtype``, widened)."""
    w = {
        name: W.draw_normal(key, dims, name, layer, dtype).astype(jnp.float32)
        for name in W.LAYER_TENSORS
    }
    w["ln1"] = w["ln2"] = jnp.ones((dims.hidden,), jnp.float32)
    return w


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(key, tokens, dims):
    q = W.draw_int8(key, dims, "embed", 0)
    return q[tokens].astype(jnp.float32) * W.int8_scale(dims, "embed")


@functools.partial(jax.jit, static_argnames=("dims", "lowp"))
def _serving_layer(key, x, layer, dims, lowp):
    return layer_forward(x, serving_layer_weights(key, dims, layer), dims, lowp)


@functools.partial(jax.jit, static_argnames=("dims", "lowp", "first", "count"))
def _head_gaps(key, x, probe, dims, lowp, first, count):
    x = rms_norm(x, jnp.ones((dims.hidden,), jnp.float32), dims.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = W.draw_int8(key, dims, "lm_head", 0).astype(jnp.float32)
    logits = _mm("bsd,dv->bsv", x, w * W.int8_scale(dims, "lm_head"), lowp)
    got = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return logits.max(-1) - got, jnp.argmax(logits, -1).astype(jnp.int32)


def served_logit_gaps(
    seed: int, dims: W.Dims, tokens: jax.Array, first: int, count: int,
    lowp: bool = False, probe=None,
):
    """Teacher-forced forward over ``tokens`` [B, T] (each row a prompt
    followed by the tokens that were served for it, then padding).

    Returns ``(gap, top)``, each [B, count]. Position ``first + j`` of a
    row predicts served token ``j``, which sits at ``first + j + 1``:
    ``gap[b, j]`` is how far that token's logit lies below the row's best
    logit there (0 where the served token is the reference's own first
    choice), and ``top[b, j]`` is the reference's first choice. With
    ``probe`` [B, count] the gap is read for those tokens instead of the
    served ones (the control's first choices), the inputs unchanged.

    The seed's key is an argument of every jitted function, never a
    constant inside one: a new seed must find its programs compiled.
    """
    key = W.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    if probe is None:
        probe = tokens[:, first + 1: first + 1 + count]
    x = _embed(key, tokens, dims)
    for layer in range(dims.layers):
        x = _serving_layer(key, x, layer, dims, lowp)
    return _head_gaps(
        key, x, jnp.asarray(probe, jnp.int32), dims, lowp, first, count
    )


# ----------------------------------------------------------------- training


def _xent(x, head_w, targets, mask, lowp):
    """Sum of masked next-token cross-entropies over [S, D] positions."""
    logits = _mm("sd,dv->sv", x, head_w, lowp)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - got) * mask)


class TrainingReference:
    """Loss and gradients of the first step and the loss of the second,
    after one AdamW update, layer by layer in float32.

    AdamW's first update needs no moments: with ``m = (1-b1) g`` and
    ``v = (1-b2) g*g`` bias-corrected, it is
    ``-lr * (g / (|g| + eps) + wd * p)``. So the reference applies it as
    each layer's gradient appears and keeps only the updated weights.
    """

    def __init__(self, seed: int, dims: W.Dims, dtype, opt: dict,
                 lowp: bool = False, head_block: int = 2048,
                 devices=None) -> None:
        self.dims = dims
        # Where a cell has several chips the layers are dealt round them,
        # for their memory alone: every layer still runs on one device,
        # in float32, one after another.
        self.devices = list(devices) if devices else [jax.devices()[0]]
        self.key = W.seed_key(seed)
        d = dims

        @jax.jit
        def fwd(x, w):
            return layer_forward(x, w, d, lowp)

        @jax.jit
        def bwd(x, w, dy):
            _, vjp = jax.vjp(lambda x, w: layer_forward(x, w, d, lowp), x, w)
            return vjp(dy)

        @functools.partial(jax.jit, static_argnames=("n_tokens",))
        def head_loss(x, ln_f, head_w, targets, mask, n_tokens):
            def loss(x, ln_f, head_w):
                h = rms_norm(x, ln_f, d.rms_eps).reshape(-1, d.hidden)
                t, m = targets.reshape(-1), mask.reshape(-1)
                total = 0.0
                block = jax.checkpoint(
                    lambda h, t, m, hw: _xent(h, hw, t, m, lowp)
                )
                for i in range(0, h.shape[0], head_block):
                    sl = slice(i, i + head_block)
                    total = total + block(h[sl], t[sl], m[sl], head_w)
                return total / n_tokens

            return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, ln_f, head_w)

        @jax.jit
        def update(old, grad):
            """AdamW's first step on one tensor: the new tensor, the
            gradient's norm and the norm of the change."""
            new = old - opt["learning_rate"] * (
                grad / (jnp.abs(grad) + opt["eps"]) + opt["weight_decay"] * old
            )
            diff = new - old
            return (new, jnp.sqrt(jnp.sum(grad * grad)),
                    jnp.sqrt(jnp.sum(diff * diff)))

        self._fwd, self._bwd, self._head, self._update = (
            fwd, bwd, head_loss, update
        )
        # The seed's key is an argument, never a constant inside a
        # program: a new seed must find its programs compiled.
        self._layer0 = jax.jit(
            lambda key, layer: training_layer_weights(key, d, layer, dtype)
        )
        self._table = jax.jit(
            lambda key: tuple(
                W.draw_normal(key, d, n, 0, dtype).astype(jnp.float32)
                for n in ("embed", "lm_head")
            )
        )

    def _home(self, layer: int):
        return self.devices[layer % len(self.devices)]

    def _initial(self) -> dict:
        embed, head = jax.device_put(self._table(self.key), self.devices[0])
        return {
            "embed": embed, "lm_head": head,
            "ln_f": jax.device_put(
                jnp.ones((self.dims.hidden,), jnp.float32), self.devices[0]
            ),
            "layers": [
                jax.device_put(self._layer0(self.key, l), self._home(l))
                for l in range(self.dims.layers)
            ],
        }

    def _forward(self, p: dict, tokens):
        d = self.dims
        tokens = jnp.asarray(tokens, jnp.int32)
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.pad(
            jnp.ones(tokens[:, 1:].shape, jnp.float32), ((0, 0), (0, 1))
        )
        n_tokens = int(tokens.shape[0] * (tokens.shape[1] - 1))
        xs = [p["embed"][tokens]]
        for l in range(d.layers):
            xs[-1] = jax.device_put(xs[-1], self._home(l))
            xs.append(self._fwd(xs[-1], p["layers"][l]))
        loss, (dx, g_lnf, g_head) = self._head(
            jax.device_put(xs[-1], self.devices[0]), p["ln_f"], p["lm_head"],
            targets, mask, n_tokens,
        )
        return tokens, xs, float(loss), dx, g_lnf, g_head

    def run(self, first, second=None) -> dict:
        """``first`` [B, S]: the rows of the first step. ``second``: the
        rows of the second, whose loss is taken after one update."""
        p = self._initial()
        tokens, xs, loss1, dx, g_lnf, g_head = self._forward(p, first)
        norms, change = {}, {}

        def update(name, old, grad):
            new, norms[name], change[name] = self._update(old, grad)
            return new

        p["lm_head"] = update("lm_head", p["lm_head"], g_head)
        p["ln_f"] = update("ln_f", p["ln_f"], g_lnf)
        del g_head
        for l in reversed(range(self.dims.layers)):
            # Each layer's gradient is used as it appears and dropped.
            dx, gw = self._bwd(
                xs[l], p["layers"][l], jax.device_put(dx, self._home(l))
            )
            xs[l + 1] = None
            p["layers"][l] = {
                n: update(f"layers.{n}.{l}", p["layers"][l][n], gw[n])
                for n in gw
            }
            del gw
        dx = jax.device_put(dx, self.devices[0])
        g_embed = jnp.zeros_like(p["embed"]).at[tokens].add(dx)
        p["embed"] = update("embed", p["embed"], g_embed)
        del g_embed, dx, xs
        norms = {k: float(v) for k, v in norms.items()}
        change = {k: float(v) for k, v in change.items()}
        out = {"loss1": loss1, "grad_norm": norms, "change_norm": change}
        if second is not None:
            out["loss2"] = self._forward(p, second)[2]
        return out
