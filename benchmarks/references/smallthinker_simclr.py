"""The plain reference for ``smallthinker-21ba3b-enc``: three contrastive
training steps of a SmallThinker block stack as a document encoder, in
float32, and the comparison that holds a run to them.

As for the ResNets (``resnet_simclr.py``, whose NT-Xent, head batch norm,
warm-up rate, compared numbers and ``judge`` are used here as they stand),
what is compared is the timed object itself: ``watch`` records what the
trainer's one step function was fed and gave back in its first three steps,
``compare`` runs the same three steps here from the same initial parameters
on the same packed views. Everything above ``watch`` is ``jax.numpy`` and
``jax.lax`` only and imports nothing of the program.

One layer, from the model's ``config.json`` (the configuration's file holds
the keys; PowerInfer/SmallThinker-21BA3B-Instruct), ``h`` (tokens, hidden)
the layer's input:

    r = h W_r;  p = softmax(r) over all router outputs   (the router reads
        the layer's INPUT, un-normed: the family's "router before attention")
    w, e = the ``moe_num_active_primary_experts`` largest of p; w = w / sum(w)
    a = RMSNorm(h);  q, k, v = a W_q, a W_k, a W_v;  query head i reads
        key-value head i // (heads / kv heads)
    ``rope_layout`` 0: no rotary embedding, causal attention over the whole
        document; 1: rotary embedding (half-split pairs, ``rope_theta``) on q
        and k at the token's position in its document, causal attention over
        the last ``sliding_window_size`` positions, the token's own included
    h1 = h + softmax(q k^T / sqrt(head_dim) + mask) v W_o
    m = RMSNorm(h1)
    y = sum over the chosen experts e THAT ARE HELD HERE of
        w_e (relu(m W_gate,e) * (m W_up,e)) W_down,e
    out = h1 + y

The held experts' share is the ``model-configs`` guide's section 4: the
router keeps every output and its experts per token, a token's weights are
normalised over its chosen experts held or not, and what the experts that
live on the deployment's other chips would add is left out, here as in the
program; that partial result goes on to the next layer. The encoder's
output for a document is the mean of the final RMS-normed hidden states over
its tokens; then the projection head the ResNets use (Linear, BN, ReLU,
Linear without bias), NT-Xent over the step's documents, and LARS as Apex
LARC around momentum SGD (``resnet_simclr.lars_update``'s arithmetic, weight
decay off every leaf of one axis: the norm scales and the head's biases).
No language-model head: the objective has none.

Memory, at 4 rows of 8,192 tokens and 566 M parameters on one chip: one
head's scores over one row are 268 MB in float32, so attention runs head by
head and row by row (``lax.map`` over a rematerialised body), the experts
run over chunks of tokens, each layer is under ``jax.checkpoint``, and the
optimizer's update is a program of its own that overwrites what it is given.
None of that changes the arithmetic.

Fed from the program and so not covered: the initial parameters (its draw
from the seed, read by name) and the packed views of each step (its span
crops from the step's own key: token ids, segment ids, positions, lengths).

The control and the planted faults (``reference_trajectory``'s keywords, for
``smallthinker_calibrate.py`` and the tests): ``precision="float8"`` rounds
the inputs of every matrix product; ``rows=0.5`` leaves half of a step's
documents out (the later rows of each view); ``fault`` is one of
``no_renorm`` (the routing weights as the softmax gave them),
``rope_on_global`` (rotary embedding on the layout-0 layers too),
``no_window`` (the window ignored) and ``router_normed`` (the router reads the
normed input); ``frozen`` makes of a trajectory the one in which the state was
left unchanged.
"""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.references.first_steps import FirstSteps, StopAfterCapture, momentum_of
from benchmarks.references.resnet_simclr import (
    PRECISIONS,
    batch_norm,
    dense,
    errors,
    judge,
    learning_rate,
    nt_xent,
    recipe_of,
)

STEPS = 3
FAULTS = ("no_renorm", "rope_on_global", "no_window", "router_normed")
EXPERT_CHUNK = 2048  # tokens the experts take at a time


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    """``x`` (R, L, heads, D) turned at ``positions`` (R, L): pairs
    ``(i, i + D/2)``, angle ``position * theta ** (-2i / D)``."""
    half = x.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half
    )
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, segments, window, dtype):
    """Causal softmax attention inside each document of each row, over the
    last ``window`` positions if given; ``q`` (R, L, heads, D), ``k`` and
    ``v`` (R, L, kv heads, D). One head of one row at a time."""
    rows, length, heads, d = q.shape
    group = heads // k.shape[2]

    def one(args):
        qh, kh, vh, seg = args
        scores = dense(qh, kh.T, dtype) / jnp.sqrt(jnp.float32(d))
        # the mask from iotas, inside the body: as a closed-over constant it
        # is 67 MB at 8,192 and the compiler keeps a copy for every use
        at = lax.iota(jnp.int32, length)
        ok = (at[:, None] >= at[None, :]) & (seg[:, None] == seg[None, :])
        if window is not None:
            ok &= at[:, None] - at[None, :] < window
        weights = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
        return dense(weights, vh, dtype)

    def by_head(x, repeat):  # (R, L, n, D) -> (R * heads, L, D)
        x = jnp.repeat(x.transpose(0, 2, 1, 3), repeat, axis=1)
        return x.reshape(rows * heads, length, d)

    out = lax.map(jax.checkpoint(one), (
        by_head(q, 1), by_head(k, group), by_head(v, group),
        jnp.repeat(segments, heads, axis=0),
    ))
    return out.reshape(rows, heads, length, d).transpose(0, 2, 1, 3)


def held_share(m, weights, chosen, gate, up, down, first, dtype):
    """``sum_e w_e (relu(m gate_e) * (m up_e)) down_e`` over the held experts
    ``first .. first + held - 1``; ``weights``, ``chosen`` (T, per token)."""
    held = gate.shape[0]
    # (T, held): a token's weight on each held expert, 0 where not chosen
    dense_w = jnp.sum(
        weights[..., None] * (chosen[..., None] == first + jnp.arange(held)), axis=1
    )
    tokens, width = m.shape
    chunk = min(EXPERT_CHUNK, tokens)

    def some(args):
        mc, wc = args  # a chunk of tokens through the held experts in turn

        def add(out, expert):
            w_gate, w_up, w_down, w = expert
            hidden = jax.nn.relu(dense(mc, w_gate, dtype)) * dense(mc, w_up, dtype)
            return out + w[:, None] * dense(hidden, w_down, dtype), None

        return lax.scan(jax.checkpoint(add), jnp.zeros_like(mc), (gate, up, down, wc.T))[0]

    out = lax.map(jax.checkpoint(some), (
        m.reshape(tokens // chunk, chunk, width),
        dense_w.reshape(tokens // chunk, chunk, held),
    ))
    return out.reshape(tokens, width)


def layer(shape, windowed, dtype, fault):
    """One layer as ``f(h, p, segments, positions, forced) -> (h, chosen)``;
    ``p`` the layer's parameters by the names the trainer's tree gives them,
    ``chosen`` (tokens, per token) the experts the router picked. ``forced``,
    if not ``None``, is taken in their place (a token's weights are then its
    probabilities on those): for the unwindowed reference of
    :func:`no_window_share`, which has to keep the reference's routing."""
    eps, heads, kv, d = shape["eps"], shape["heads"], shape["kv_heads"], shape["head_dim"]
    turn = windowed or fault == "rope_on_global"
    window = shape["window"] if windowed and fault != "no_window" else None

    def apply(h, p, segments, positions, forced):
        rows, length, width = h.shape
        a = rms_norm(h, p["attention_norm"], eps)
        scored = a if fault == "router_normed" else h
        probs = jax.nn.softmax(dense(scored.reshape(-1, width), p["router"], dtype), axis=-1)
        weights, chosen = lax.top_k(probs, shape["per_token"])
        if forced is not None:
            weights, chosen = jnp.take_along_axis(probs, forced, axis=-1), forced
        if fault != "no_renorm":
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        q = dense(a, p["q"], dtype).reshape(rows, length, heads, d)
        k = dense(a, p["k"], dtype).reshape(rows, length, kv, d)
        v = dense(a, p["v"], dtype).reshape(rows, length, kv, d)
        if turn:
            q, k = rotary(q, positions, shape["theta"]), rotary(k, positions, shape["theta"])
        out = attention(q, k, v, segments, window, dtype)
        h = h + dense(out.reshape(rows, length, heads * d), p["o"], dtype)
        m = rms_norm(h, p["experts_norm"], eps).reshape(-1, width)
        y = held_share(
            m, weights, chosen, p["gate"], p["up"], p["down"], shape["first"], dtype
        )
        return h + y.reshape(rows, length, width), chosen

    return jax.checkpoint(apply)


def embed(shape, params, stats, view, dtype, fault, forced=None):
    """One view's ``z`` (documents, d), the head's running statistics after
    it and every layer's chosen experts; ``view`` is (tokens, segments,
    positions, lengths), ``forced`` a choice per layer to take instead."""
    tokens, segments, positions, lengths = view
    f = params["f"]
    h = f["embedding"][tokens]
    choices = []
    for i, kind in enumerate(shape["layout"]):
        h, chosen = layer(shape, bool(kind), dtype, fault)(
            h, f[f"layer_{i}"], segments, positions, None if forced is None else forced[i])
        choices.append(chosen)
    h = rms_norm(h, f["final_norm"], shape["eps"])
    member = (segments[..., None] == jnp.arange(lengths.shape[-1])).astype(jnp.float32)
    pooled = jnp.einsum("rls,rlh->rsh", member, h, precision=lax.Precision.HIGHEST)
    pooled = (pooled / lengths[..., None].astype(jnp.float32)).reshape(-1, h.shape[-1])
    g = params["g"]
    y = dense(pooled, g["linear1"]["kernel"], dtype) + g["linear1"]["bias"]
    y, bn1 = batch_norm(y, g["bn1"], stats["g"]["bn1"])
    return dense(jax.nn.relu(y), g["linear2"]["kernel"], dtype), {"g": {"bn1": bn1}}, choices


def lars_update(p, g, m, lr, recipe):
    wd = recipe["decay"] if p.ndim > 1 else 0.0
    p_norm, g_norm = jnp.linalg.norm(p), jnp.linalg.norm(g)
    trust = recipe["trust"] * p_norm / (g_norm + wd * p_norm + 1e-8)
    u = jnp.where((p_norm > 0) & (g_norm > 0), (g + wd * p) * trust, g)
    m = recipe["momentum"] * m + u
    return p - lr * m, m


def make_step(shape, recipe, precision="float32", fault=None):
    """One step's two compiled programs, built once per process and
    variant: at the cell's size each takes a minute or more to compile."""
    numbers = tuple(sorted((k, v) for k, v in recipe.items() if not isinstance(v, str)))
    return _make_step(tuple(sorted(shape.items())), numbers, precision, fault)


@functools.lru_cache(maxsize=None)
def _make_step(shape, recipe, precision, fault):
    """``step(params, stats, momentum, v0, v1, lr, forced=None) -> (loss,
    params, stats, momentum, grad_norms, choices)``, ``momentum`` a tree on
    the host, ``choices`` the experts every layer's router picked in each view
    (``forced``, the same shape, is taken in their place). Two compiled
    programs, so that the optimizer's copies and the backward pass's
    activations are never on the chip together: the loss with its gradient
    (12.2 GB at the cell's size), then the update, which overwrites the
    parameters and the gradient it is given."""
    shape, recipe = dict(shape), dict(recipe)
    dtype = PRECISIONS[precision]

    def loss_fn(params, stats, v0, v1, forced):
        z0, stats, c0 = embed(shape, params, stats, v0, dtype, fault, forced and forced[0])
        z1, stats, c1 = embed(shape, params, stats, v1, dtype, fault, forced and forced[1])
        return nt_xent(z0, z1, recipe["temperature"]), (stats, (c0, c1))

    gradient = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    def update(params, grads, momentum, lr):
        norms = jax.tree.map(jnp.linalg.norm, grads)
        moved = jax.tree.map(
            lambda p, g, m: lars_update(p, g, m, lr, recipe), params, grads, momentum
        )
        pick = lambda i: jax.tree.map(lambda _, pm: pm[i], params, moved)  # noqa: E731
        return pick(0), pick(1), norms

    update = jax.jit(update, donate_argnums=(0, 1))

    def step(params, stats, momentum, v0, v1, lr, forced=None):
        # the momentum waits on the host while the gradient's program runs
        (loss, (stats, choices)), grads = gradient(params, stats, v0, v1, forced)
        params, momentum, norms = update(params, grads, momentum, lr)
        return loss, params, stats, jax.device_get(momentum), norms, choices

    return step


def three_steps(
    shape, recipe, params, stats, views, rates, precision="float32", fault=None, forced=None
):
    """The reference's trajectory over ``views`` (a ``(v0, v1)`` per step),
    as ``resnet_simclr.three_steps`` gives it, every tree on the host, and
    the first step's routing (``choices1``); ``forced`` is the routing the
    first step has to take."""
    step = make_step(shape, recipe, precision, fault)
    momentum = jax.tree.map(lambda p: np.zeros(p.shape, p.dtype), params)
    params = jax.tree.map(jnp.array, params)  # the update overwrites its own copy
    out = {"loss": []}
    for k, ((v0, v1), lr) in enumerate(zip(views, rates)):
        loss, params, stats, momentum, norms, choices = step(
            params, stats, momentum, v0, v1, lr, forced if k == 0 else None)
        out["loss"].append(float(loss))
        if k == 0:
            out["momentum1"], out["grad_norms1"] = momentum, jax.device_get(norms)
            out["choices1"] = jax.device_get(choices)
    out["params"], out["stats"] = jax.device_get((params, stats))
    return out


def shape_of(config: dict) -> dict:
    """The layer's shapes from the configuration's file, under the model's
    own ``config.json`` keys."""
    layers = int(config["num_hidden_layers"])
    if config["rope_layout"][:layers] != config["sliding_window_layout"][:layers]:
        raise ValueError("rotary embedding and the window do not go together here")
    return {
        "layout": tuple(config["rope_layout"][:layers]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "per_token": int(config["moe_num_active_primary_experts"]),
        "first": int(config["as_run"].get("first_expert", 0)),
    }


# -- the glue to the harness (the only part that touches the program) --------

class _Recorded:
    """The trainer's step function, its first calls kept on the host."""

    def __init__(self, fn, into: "HostFirstSteps"):
        self._fn = fn
        self._into = into

    def __getattr__(self, name):  # ``compile``, ``lower``: the wrapped one's
        return getattr(self._fn, name)

    def __call__(self, state, images, rng):
        into = self._into
        n = len(into.steps)
        if n >= into.keep:
            if into.stop:
                raise StopAfterCapture()
            return self._fn(state, images, rng)
        if n == 0:  # before the step donates them
            into.params0, into.stats0 = jax.device_get((state.params, state.batch_stats))
        rows = jax.device_get(images)
        new_state, metrics = self._fn(state, images, rng)
        into.steps.append({"images": rows, "rng": rng, "loss": metrics["loss"]})
        if n == 0:
            into.momentum1 = jax.device_get(momentum_of(new_state.opt_state))
        if n + 1 == into.keep:
            into.params_end, into.stats_end = jax.device_get(
                (new_state.params, new_state.batch_stats)
            )
            into.remove()
        return new_state, metrics


class HostFirstSteps(FirstSteps):
    """``first_steps.FirstSteps`` for a state of gigabytes: it stands where
    the entry point looks up its step builder and wraps the one function the
    builder returns, as its parent does, but keeps what it keeps on the host,
    fetched as it goes (the parameters before step 1, the momentum after it,
    the parameters after the last kept step: 2.3 GB each here, which the
    device has no room for beside the step). The fetches wait for the steps
    they follow; all of it is over at the third step, in the epoch before the
    window's first event, and the watch then takes itself out of the entry
    point. It also keeps the builder's ``crop_lengths``, which the views are
    made from again."""

    crop_lengths: tuple[int, ...] = ()

    def _build(self, model, tx, mesh, **kwargs):
        self.crop_lengths = tuple(kwargs.get("crop_lengths", ()))
        return _Recorded(self._builder(model, tx, mesh, **kwargs), self)


def watch(cell: dict, opts: dict, entry):
    """Before the trainer starts: record its first steps."""
    return HostFirstSteps(entry, keep=STEPS).install()


def two_views(images, rng, crop_lengths):
    """The step's two packed views of a global batch of documents, from the
    step's own key, through the program's span crops: ``key[v, i] =
    fold_in(rng, v * N + i)`` by global row."""
    from simclr_tpu.data.documents import span_crop_views

    n = images.shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(2 * n, dtype=jnp.int32))
    return span_crop_views(keys, images, crop_lengths)


def program_trajectory(seen) -> dict:
    return {
        "loss": [float(s["loss"]) for s in seen.steps],
        "momentum1": seen.momentum1,
        "params": seen.params_end,
        "stats": seen.stats_end,
    }


def reference_trajectory(
    cell: dict, seen, precision="float32", rows=1.0, fault=None, steps=STEPS, forced=None
) -> dict:
    """The reference over the first ``steps`` recorded steps' views; the
    other keywords are the control and the planted faults (module
    docstring), and the routing its first step has to take."""
    recipe = recipe_of(cell)
    pack = jax.jit(two_views, static_argnums=2)
    views = []
    for s in seen.steps[:steps]:
        pair = pack(jnp.asarray(s["images"]), s["rng"], seen.crop_lengths)
        views.append(tuple(
            tuple(x[: max(1, int(x.shape[0] * rows))] for x in view) for view in pair
        ))
    rates = [
        learning_rate(k, recipe, cell["traffic"]["batch_per_chip"], int(cell["chips"]))
        for k in range(len(views))
    ]
    return three_steps(
        shape_of(cell["config"]), recipe, seen.params0, seen.stats0, views, rates,
        precision, fault, forced,
    )


def _along(ours, theirs, other):
    """Per leaf, ``(<e, d>, <d, d>, <e, e>)`` with ``e = ours - theirs`` and
    ``d = other - theirs``, in float64 on the host."""
    out = []
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (ours, theirs, other))):
        b = np.asarray(b, np.float64).ravel()
        e, d = np.asarray(a, np.float64).ravel() - b, np.asarray(c, np.float64).ravel() - b
        out.append((float(np.dot(e, d)), float(np.dot(d, d)), float(np.dot(e, e))))
    return out


def no_window_share(ours: dict, theirs: dict, unwindowed: dict, detail=None) -> float:
    """How much of the step an unwindowed encoder would take away from the
    reference's the program took: with ``d`` the unwindowed reference's first
    gradient (as LARS hands it on) less the reference's and ``e`` the
    program's less the reference's, ``|<e, d>| / <d, d>`` over all leaves.

    The window cuts 512 of a 4,608-token crop's positions, in 4 of a step's
    256 crops: ignoring it moves the first gradient by 1 - cosine 2e-4, a
    twentieth of what bfloat16 rounding alone moves it (4e-3), so no distance
    from the reference shows it; its own direction might. The unwindowed
    reference keeps the reference's routing (``forced``), so that ``d`` holds
    no token whose nearly tied experts swapped. **On the chip the number
    does not separate** (PR 34): the unwindowed reference in the program's
    place reads 0.96 to 1.01, and the sound program 0.25 to 0.56 over five
    seeds (0.07 to 0.41 over thirteen with the routing left free), the same
    sign on nearly every leaf: the program lies a quarter to a half of the
    way along ``d``. That is bfloat16's doing and not the program's: this
    file's own steps with bfloat16 product inputs read the same against
    their float32 selves, from the rounding on the value path alone (PERF.md
    section 7). So the benchmark's cell does not list it and holds the
    window by ``window_kernel``; the toy cell lists both (this one reads
    under 0.04 sound there and 1.0 planted)."""
    leaves = _along(ours["momentum1"], theirs["momentum1"], unwindowed["momentum1"])
    if detail is not None:
        detail["no_window_momentum1"] = leaves
    norm = sum(dd for _, dd, _ in leaves)
    return abs(sum(ed for ed, _, _ in leaves)) / norm if norm > 0 else 0.0


def _plain_attention(q, k, v, segments, window):
    return attention(q, k, v, segments, window, None)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _sum_and_gradients(f, window, q, k, v, cot, segments):
    """``f``'s attention and the gradients of ``<result, cot>`` by q, k, v."""
    out, pull = jax.vjp(lambda q, k, v: f(q, k, v, segments, window), q, k, v)
    return (out, *pull(cot))


def window_kernel(cell: dict, seen, fault=None) -> float:
    """How far the program's attention, as it is compiled on this backend at
    the cell's shapes, lies along the way from the windowed sum to the
    unwindowed one: the largest, over the result and its three gradients, of
    ``|<e, d>| / <d, d>``, with ``d`` this file's float32 attention with the
    window ignored less with it and ``e`` the program's
    ``models/attention.attention`` less the latter.

    Where ``no_window_share`` looks for the window in a whole step's
    gradient, which holds 512 affected positions among 32,768, this looks at
    the one function that cuts it, on the positions it cuts: the first
    recorded row of the first step (one crop of it is longer than the
    window), the configuration's heads and window, q, k, v and the
    cotangent drawn from the step's own key in bfloat16. On a TPU that is
    the splash kernel with its ``LocalMask``, forward and both backward
    kernels; a mask that lets the far keys in reads 1, a sound kernel what
    bfloat16 rounding leaves along ``d``. ``fault="no_window"`` plants it:
    the program's attention asked for no window, as a ``CausalMask`` put in
    the ``LocalMask``'s place would be. It does not see how the layers call
    the function (``tests/test_sequence_encoder.py`` does, on the CPU)."""
    from simclr_tpu.models.attention import attention as programs

    shape = shape_of(cell["config"])
    step = seen.steps[0]
    view = jax.jit(two_views, static_argnums=2)(
        jnp.asarray(step["images"]), step["rng"], seen.crop_lengths)[0]
    segments = view[1][:1]
    length, d = segments.shape[1], shape["head_dim"]
    keys = jax.random.split(jax.random.fold_in(step["rng"], length), 4)
    q, k, v, cot = (
        jax.random.normal(key, (1, length, n, d), jnp.float32).astype(jnp.bfloat16)
        for key, n in zip(keys, (shape["heads"], shape["kv_heads"], shape["kv_heads"], shape["heads"]))
    )

    def parts(f, window, dtype):
        found = _sum_and_gradients(f, window, *(x.astype(dtype) for x in (q, k, v, cot)), segments)
        return [np.asarray(x, np.float64).ravel() for x in found]

    ours = parts(programs, None if fault == "no_window" else shape["window"], jnp.bfloat16)
    theirs = parts(_plain_attention, shape["window"], jnp.float32)
    other = parts(_plain_attention, None, jnp.float32)
    shares = []
    for a, b, c in zip(ours, theirs, other):
        dd = float(np.dot(c - b, c - b))
        shares.append(abs(float(np.dot(a - b, c - b))) / dd if dd > 0 else 0.0)
    return max(shares)


def distances(
    cell: dict, seen, ours: dict, theirs: dict, detail=None, share=True, kernel=True, fault=None
) -> dict:
    """Every number a cell may compare: ``resnet_simclr.errors``' ten; with
    ``share``, ``no_window_share``, for which the reference takes the first
    step once more with the window ignored and its own routing (a minute at
    the benchmark's cell); with ``kernel``, ``window_kernel`` (seconds), which
    reads the program itself and so is told the ``fault`` planted. ``compare``
    asks for each of the two only where the cell's limits name it."""
    found = errors(ours, theirs, seen.params0, detail)
    if share:
        unwindowed = reference_trajectory(
            cell, seen, fault="no_window", steps=1, forced=theirs["choices1"])
        found["no_window_share"] = no_window_share(ours, theirs, unwindowed, detail)
    if kernel:
        found["window_kernel"] = window_kernel(cell, seen, fault)
    return found


def frozen(theirs: dict, seen) -> dict:
    """A trajectory in which the state was left unchanged: the reference's
    losses, no momentum, the parameters and statistics as they began."""
    return {
        **theirs, "momentum1": jax.tree.map(np.zeros_like, seen.params0),
        "params": seen.params0, "stats": seen.stats0,
    }


def compare(cell: dict, opts: dict, run: dict) -> dict:
    """After the trainer has left: the reference's three steps beside the
    recorded ones. ``reasons`` is empty where every number is within its
    limit."""
    t0 = time.monotonic()
    seen = run.get("first_steps")
    if seen is None or len(seen.steps) < STEPS:
        n = 0 if seen is None else len(seen.steps)
        return {
            "reasons": [f"reference: the trainer ran {n} of the {STEPS} steps to compare"],
            "errors": {}, "seconds": time.monotonic() - t0,
        }
    gc.collect()  # the trainer's state and batches, before the reference's
    limits = cell["data"]["reference"]["limits"]
    found = distances(
        cell, seen, program_trajectory(seen), reference_trajectory(cell, seen),
        share="no_window_share" in limits, kernel="window_kernel" in limits,
    )
    beside, reasons = judge(found, limits)
    return {
        "reasons": reasons, "errors": beside, "all": found,
        "seconds": time.monotonic() - t0,
    }
