"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B's decoder
(`model_type: nemotron_h`): `jax.numpy`, float32, matmul precision
"highest", no kernels, no cache, no chunks, no batching. Imports nothing
from `oryx_tpu` and takes NOTHING from its `LLMConfig`: `sizes` is a
plain dict made of the source's own keys (the configuration file's,
`sizes_from_keys`). One layer's weights are made float32 at a time, so
the whole fits beside the served bfloat16 ones.

With d = hidden_size and hidden state h [T, d], layer i of
num_hidden_layers has ONE sublayer, by character i of
hybrid_override_pattern:

    h = h + Op_i(rms_norm(h, w_i));   logits = rms_norm(h, w_f) @ W_head

`M` (Mamba-2; nh = mamba_num_heads heads of P = mamba_head_dim, G =
n_groups, N = ssm_state_size, K = conv_kernel):

    [z | xBC | dt] = u W_in                 (nh P | nh P + 2 G N | nh)
    xBC_t = silu(b + sum_{j<K} w[j] * xBC_{t-K+1+j})   (zeros before 0)
    [x | B | C] = xBC ;  D_t,h = softplus(dt_t,h + dt_bias_h)
    S_t,h = exp(D_t,h A_h) S_t-1,h + D_t,h x_t,h (outer) B_t,g   [P, N]
    y_t,h = S_t,h C_t,g + Dskip_h x_t,h         A_h = -exp(A_log_h),
                                                g = h // (nh / G)
    Op = (group_rms_norm(y * silu(z)) * w_norm) W_out

a scan over T, one token at a time (the program's prefill is the chunked
matmul form; this is what it has to equal).

`*`: q = u W_q (num_attention_heads x head_dim), k, v = u W_k, u W_v
(num_key_value_heads x head_dim; query head j reads key/value head
j // group); NO position term; causal softmax at 1 / sqrt(head_dim);
W_o. No bias.

`E` (the latent expert layer): s = sigmoid(u W_r) in float32 over the
n_routed_experts; the num_experts_per_tok experts are the top of s + b
(b for the SELECTION only; ties: the lower expert first); their weights
are s at those experts over their sum (norm_topk_prob), times
routed_scaling_factor; l = u W_dn; expert e: relu(l W1_e)^2 W2_e (not
gated); Op = (sum_e w_e E_e(l)) W_up + relu(u V1)^2 V2. THE SHARE:
`held` = (first, count) of the experts whose kernels `params` holds (the
program's own share); a chosen expert outside it adds nothing, before
W_up, and the weights' sum still runs over all the chosen. `forced`:
the experts are HANDED IN (a program's own choice at every position and
layer), the weights still worked out here (lfm2_ref.py says why).

The params are the program's pytree (`qwen2._init_recurrent_params`):
`layers["attn"]` / `layers["mamba2"]` stacked by kind in layer order,
`layers["ffn_norm"]`, `["router"]`, `["latent"]`, `["experts"]`,
`["shared"]` the expert layers' in layer order; linear kernels
[in, out]; the conv taps [K, channels]; `lm_head` the untied head.

Departures from the published description: none known. What the
source's keys do not settle is listed under `assumed` in the
configuration file (no position term, the router on the hidden state,
dt not clamped). The multi-token-prediction module is not run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes_from_keys(keys: dict) -> dict:
    """The source's keys -> what this file reads. `experts_held` (a
    count, from `experts_first`, default 0) is the configuration file's
    own: the share of the experts the served weights hold."""
    E = keys["n_routed_experts"]
    return {
        "pattern": keys["hybrid_override_pattern"][:keys["num_hidden_layers"]],
        "heads": keys["num_attention_heads"],
        "kv_heads": keys["num_key_value_heads"],
        "head": keys["head_dim"],
        "m_heads": keys["mamba_num_heads"], "m_head": keys["mamba_head_dim"],
        "groups": keys["n_groups"], "state": keys["ssm_state_size"],
        "eps": keys["layer_norm_epsilon"],
        "top_k": keys["num_experts_per_tok"],
        "scale": float(keys["routed_scaling_factor"]),
        "norm_topk": bool(keys["norm_topk_prob"]),
        "held": (keys.get("experts_first", 0), keys.get("experts_held", E)),
    }


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def relu2_mlp(x, w1, w2):
    return jnp.square(jax.nn.relu(x @ _f32(w1))) @ _f32(w2)


def mamba2(u, mp, sz, *, state=None, return_state: bool = False):
    """u [T, d] -> [T, d], token by token. `state`: (the K - 1 conv
    inputs before token 0 [K-1, channels], S [nh, P, N]), zeros by
    default."""
    nh, P, G, N = sz["m_heads"], sz["m_head"], sz["groups"], sz["state"]
    d = nh * P
    T = u.shape[0]
    zxd = u @ _f32(mp["in_proj"]["kernel"])
    z, xBC, dt = zxd[:, :d], zxd[:, d:d + d + 2 * G * N], zxd[:, -nh:]
    w = _f32(mp["conv"]["kernel"])  # [K, channels]
    K = w.shape[0]
    before = (jnp.zeros((K - 1, xBC.shape[1]), jnp.float32)
              if state is None else _f32(state[0]))
    win = jnp.concatenate([before, xBC])
    xc = sum(w[j] * win[j:j + T] for j in range(K))
    if "bias" in mp["conv"]:
        xc = xc + _f32(mp["conv"]["bias"])
    xc = jax.nn.silu(xc)
    x = xc[:, :d].reshape(T, nh, P)
    Bm = jnp.repeat(xc[:, d:d + G * N].reshape(T, G, N), nh // G, axis=1)
    Cm = jnp.repeat(xc[:, d + G * N:].reshape(T, G, N), nh // G, axis=1)
    D = jax.nn.softplus(dt + _f32(mp["dt_bias"]))  # [T, nh]
    A = -jnp.exp(_f32(mp["A_log"]))

    def step(S, t):
        x_t, B_t, C_t, D_t = t
        S = jnp.exp(D_t * A)[:, None, None] * S \
            + (D_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    S0 = (jnp.zeros((nh, P, N), jnp.float32) if state is None
          else _f32(state[1]))
    S1, y = jax.lax.scan(step, S0, (x, Bm, Cm, D))
    y = (y + _f32(mp["D"])[:, None] * x).reshape(T, d)
    g = (y * jax.nn.silu(z)).reshape(T, G, d // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + sz["eps"])
    out = (g.reshape(T, d) * _f32(mp["norm"]["weight"])) @ _f32(
        mp["out_proj"]["kernel"])
    return (out, (win[T:], S1)) if return_state else out


def attention(u, ap, sz):
    T = u.shape[0]
    Hq, Hk, D = sz["heads"], sz["kv_heads"], sz["head"]
    q = (u @ _f32(ap["q_proj"]["kernel"])).reshape(T, Hq, D)
    k = (u @ _f32(ap["k_proj"]["kernel"])).reshape(T, Hk, D)
    v = (u @ _f32(ap["v_proj"]["kernel"])).reshape(T, Hk, D)
    k = jnp.repeat(k, Hq // Hk, axis=1)
    v = jnp.repeat(v, Hq // Hk, axis=1)
    seen = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):  # one head at a time: [T, T] scores, not [Hq, T, T]
        qh, kh, vh = qkv
        s = jnp.where(seen, (qh @ kh.T) * D ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(o, 0, 1).reshape(T, Hq * D) @ _f32(
        ap["o_proj"]["kernel"])


def route(x, router, sz, chosen=None):
    """x [T, d] -> (weights [T, E] float32, 0 at the experts that were
    not chosen; the experts this file would choose [T, K]). `chosen`
    [T, K]: experts handed in; their weights are still this file's."""
    s = jax.nn.sigmoid(x @ _f32(router["kernel"]))
    pick = s + _f32(router["bias"]) if "bias" in router else s
    _, own = jax.lax.top_k(pick, sz["top_k"])
    idx = own if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * sz["scale"]
    return jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w), own


def routed_latent(x, ffn, sz, chosen=None):
    """The routed part BEFORE W_up: sum over the held experts of w_e
    E_e(x W_dn) [T, latent]; every held expert on every token, the
    unchosen weighed 0. Returns (it, the experts this file would
    choose)."""
    w, own = route(x, ffn["router"], sz, chosen)
    first, count = sz["held"]
    lat = x @ _f32(ffn["latent"]["down"]["kernel"])

    def one(carry, e):
        w1, w2, we = e
        return carry + we[:, None] * relu2_mlp(lat, w1, w2), None

    ex = ffn["experts"]
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(lat),
        (ex["up"], ex["down"], w[:, first:first + count].T))
    return y, own


def expert_layer(x, ffn, sz, chosen=None):
    """The whole `E` sublayer on its normed input x [T, d]."""
    y, own = routed_latent(x, ffn, sz, chosen)
    sh = ffn["shared"]
    return (y @ _f32(ffn["latent"]["up"]["kernel"])
            + relu2_mlp(x, sh["up_proj"]["kernel"],
                        sh["down_proj"]["kernel"])), own


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("kind", "sz"))
def _layer(h, lp, chosen=None, *, kind: str, sz):
    sz = dict(sz)
    with jax.default_matmul_precision("highest"):
        if kind == "E":
            y, own = expert_layer(
                rms_norm(h, lp["norm"]["weight"], sz["eps"]), lp, sz, chosen)
            return h + y, own
        u = rms_norm(h, lp["input_norm"]["weight"], sz["eps"])
        if kind == "*":
            return h + attention(u, lp, sz), None
        return h + mamba2(u, lp["mixer"], sz), None


FFN_STACKS = ("router", "latent", "experts", "shared")


def hidden(params, sizes: dict, ids, forced=None):
    """ids [T] -> (the last layer's output [T, d], the experts chosen
    [expert layers, T, K]), every layer in order over the whole
    sequence. `forced` [expert layers, T, K]: the experts each expert
    layer is handed (`route`); what is returned is still what this file
    WOULD choose, on the states the forced forward reaches."""
    sz = tuple(sorted(sizes.items()))
    h = _f32(params["embed"]["weight"][jnp.asarray(ids)])
    layers = params["layers"]
    seen = {"M": 0, "*": 0, "E": 0}
    chose = []
    for kind in sizes["pattern"]:
        n, chosen = seen[kind], None
        seen[kind] += 1
        if kind == "E":
            lp = dict({k: _at(layers[k], n) for k in FFN_STACKS},
                      norm=_at(layers["ffn_norm"], n))
            if forced is not None:
                chosen = jnp.asarray(forced[n], jnp.int32)
        else:
            lp = _at(layers["attn" if kind == "*" else "mamba2"], n)
        h, own = _layer(h, lp, chosen, kind=kind, sz=sz)
        if own is not None:
            chose.append(own)
    return h, chose


def logits(params, sizes: dict, ids, *, rows=None, forced=None,
           return_chosen: bool = False):
    """ids [T] -> float32 logits [T, V] (or at positions `rows` only);
    with return_chosen also `hidden`'s second value."""
    h, chose = hidden(params, sizes, ids, forced)
    out = _head(
        h if rows is None else h[jnp.asarray(rows)],
        params["final_norm"]["weight"], params["lm_head"]["kernel"],
        eps=sizes["eps"])
    return (out, chose) if return_chosen else out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, norm, head, *, eps):
    """Logits over the untied head [d, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, norm, eps) @ _f32(head)
