"""HF-checkpoint ⇄ oryx_tpu weight conversion.

Reference parity: the reference loads `Qwen2ForCausalLM.from_pretrained` +
OryxViT safetensors (SURVEY.md §2 "Model builder", §5 "Checkpoint / resume").
This module is the interop path: import HF safetensors → stacked JAX pytrees,
and export back for users of the reference checkpoints.

Works from (a) an in-memory numpy state dict, or (b) a directory of
*.safetensors shards (with or without an index json). No torch required.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Iterable, Mapping

import jax.numpy as jnp
import numpy as np

from oryx_tpu.config import LLMConfig, VisionConfig

Params = dict[str, Any]
StateDict = Mapping[str, np.ndarray]


# ---------------------------------------------------------------------------
# Safetensors directory reading
# ---------------------------------------------------------------------------


def load_safetensors_dir(path: str) -> dict[str, np.ndarray]:
    """Load all tensors from a HF checkpoint directory into numpy."""
    from safetensors.numpy import load_file

    index = os.path.join(path, "model.safetensors.index.json")
    out: dict[str, np.ndarray] = {}
    if os.path.exists(index):
        with open(index) as f:
            shards = sorted(set(json.load(f)["weight_map"].values()))
        for shard in shards:
            out.update(load_file(os.path.join(path, shard)))
    else:
        for name in sorted(os.listdir(path)):
            if name.endswith(".safetensors"):
                out.update(load_file(os.path.join(path, name)))
    if not out:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    return out


def _get(sd: StateDict, key: str) -> np.ndarray:
    if key not in sd:
        raise KeyError(f"missing weight {key!r}; have e.g. "
                       f"{sorted(sd)[:5]}...")
    arr = np.asarray(sd[key])
    if arr.dtype == np.dtype("V2"):  # raw bf16 from safetensors.numpy
        import jax
        arr = np.asarray(jax.numpy.asarray(arr.view(jnp.bfloat16)))
    return arr


def _stack(
    sd: StateDict, n: int, fmt: str, post: Callable[[np.ndarray], np.ndarray]
) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(post(_get(sd, fmt.format(i)))) for i in range(n)])


# ---------------------------------------------------------------------------
# Qwen2 / Yi decoder
# ---------------------------------------------------------------------------

_T = lambda w: np.ascontiguousarray(w.T)  # torch [out,in] -> jax [in,out]
_I = lambda w: w


def import_qwen2(
    sd: StateDict, cfg: LLMConfig, dtype: jnp.dtype = jnp.float32
) -> Params:
    """HF Qwen2/Llama-family state dict → stacked pytree (models/qwen2.py).

    Accepts either `model.`-prefixed names (full ForCausalLM dict) or the
    bare inner-model names; the bare form carries no `lm_head.weight`, so it
    requires `cfg.tie_word_embeddings` (a clear KeyError otherwise).

    An expert config (`sdar_moe` / `qwen3_moe` checkpoints) maps
    `mlp.gate.weight` to the float32 router, `mlp.experts.M.{gate,up,
    down}_proj.weight` into the stacked `[L, E, in, out]` expert kernels,
    and with cfg.qk_norm `self_attn.{q,k}_norm.weight`.
    """
    p = "model." if any(k.startswith("model.") for k in sd) else ""
    L = cfg.num_layers
    lyr = p + "layers.{}."

    def stacked(suffix: str, post=_I) -> jnp.ndarray:
        return _stack(sd, L, lyr + suffix, post)

    cast = lambda x: jnp.asarray(x).astype(dtype)
    layers: Params = {
        "input_norm": {"weight": stacked("input_layernorm.weight")},
        "post_attn_norm": {"weight": stacked("post_attention_layernorm.weight")},
        "q_proj": {"kernel": stacked("self_attn.q_proj.weight", _T)},
        "k_proj": {"kernel": stacked("self_attn.k_proj.weight", _T)},
        "v_proj": {"kernel": stacked("self_attn.v_proj.weight", _T)},
        "o_proj": {"kernel": stacked("self_attn.o_proj.weight", _T)},
    }
    if cfg.num_experts:
        def experts(proj: str) -> jnp.ndarray:
            return jnp.stack([
                stacked(f"mlp.experts.{e}.{proj}.weight", _T)
                for e in range(cfg.num_experts)
            ], axis=1)

        layers["experts"] = {
            "gate": experts("gate_proj"), "up": experts("up_proj"),
            "down": experts("down_proj"),
        }
    else:
        for proj in ("gate_proj", "up_proj", "down_proj"):
            layers[proj] = {"kernel": stacked(f"mlp.{proj}.weight", _T)}
    if cfg.qk_norm:
        layers["q_norm"] = {"weight": stacked("self_attn.q_norm.weight")}
        layers["k_norm"] = {"weight": stacked("self_attn.k_norm.weight")}
    if cfg.attention_bias:
        for proj in ("q_proj", "k_proj", "v_proj"):
            layers[proj]["bias"] = stacked(f"self_attn.{proj}.bias")
    layers = {k: {kk: cast(vv) for kk, vv in v.items()}
              for k, v in layers.items()}
    if cfg.num_experts:  # the router stays float32 (qwen2.init_params)
        layers["router"] = {"kernel": jnp.asarray(
            stacked("mlp.gate.weight", _T), jnp.float32)}
    params: Params = {
        "embed": {"weight": cast(_get(sd, p + "embed_tokens.weight"))},
        "layers": layers,
        "final_norm": {"weight": cast(_get(sd, p + "norm.weight"))},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": cast(_T(_get(sd, "lm_head.weight")))}
    return params


def export_qwen2(params: Params, cfg: LLMConfig) -> dict[str, np.ndarray]:
    """Stacked pytree → HF state-dict names (fp32 numpy)."""
    out: dict[str, np.ndarray] = {}
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))
    out["model.embed_tokens.weight"] = f32(params["embed"]["weight"])
    out["model.norm.weight"] = f32(params["final_norm"]["weight"])
    if not cfg.tie_word_embeddings:
        out["lm_head.weight"] = _T(f32(params["lm_head"]["kernel"]))
    lp = params["layers"]
    names = {
        "input_layernorm.weight": (lp["input_norm"]["weight"], _I),
        "post_attention_layernorm.weight": (lp["post_attn_norm"]["weight"], _I),
        "self_attn.q_proj.weight": (lp["q_proj"]["kernel"], _T),
        "self_attn.k_proj.weight": (lp["k_proj"]["kernel"], _T),
        "self_attn.v_proj.weight": (lp["v_proj"]["kernel"], _T),
        "self_attn.o_proj.weight": (lp["o_proj"]["kernel"], _T),
    }
    if cfg.num_experts:
        names["mlp.gate.weight"] = (lp["router"]["kernel"], _T)
        for e in range(cfg.num_experts):
            for proj in ("gate", "up", "down"):
                names[f"mlp.experts.{e}.{proj}_proj.weight"] = (
                    lp["experts"][proj][:, e], _T)
    else:
        for proj in ("gate_proj", "up_proj", "down_proj"):
            names[f"mlp.{proj}.weight"] = (lp[proj]["kernel"], _T)
    if cfg.qk_norm:
        names["self_attn.q_norm.weight"] = (lp["q_norm"]["weight"], _I)
        names["self_attn.k_norm.weight"] = (lp["k_norm"]["weight"], _I)
    if cfg.attention_bias:
        for proj in ("q_proj", "k_proj", "v_proj"):
            names[f"self_attn.{proj}.bias"] = (lp[proj]["bias"], _I)
    for suffix, (stacked, post) in names.items():
        arr = f32(stacked)
        for i in range(cfg.num_layers):
            out[f"model.layers.{i}.{suffix}"] = post(arr[i])
    return out


# ---------------------------------------------------------------------------
# SigLIP-family vision tower (OryxViT)
# ---------------------------------------------------------------------------


def import_siglip(
    sd: StateDict, cfg: VisionConfig, dtype: jnp.dtype = jnp.float32
) -> Params:
    """HF `SiglipVisionModel`-layout state dict → OryxViT pytree
    (models/oryx_vit.py). Accepts optional `vision_model.` prefix."""
    p = ""
    for cand in ("vision_model.", "vision_tower.vision_model.", ""):
        if any(k.startswith(cand + "encoder.layers.0.") for k in sd):
            p = cand
            break
    L = cfg.num_layers
    lyr = p + "encoder.layers.{}."
    cast = lambda x: jnp.asarray(x).astype(dtype)

    def stacked(suffix: str, post=_I) -> jnp.ndarray:
        return _stack(sd, L, lyr + suffix, post).astype(dtype)

    def ln(prefix: str) -> Params:
        return {"weight": stacked(prefix + ".weight"),
                "bias": stacked(prefix + ".bias")}

    def dense(prefix: str) -> Params:
        return {"kernel": stacked(prefix + ".weight", _T),
                "bias": stacked(prefix + ".bias")}

    # HF stores patch embedding as Conv2d [H, C, ph, pw]; our patchify is an
    # unfold + matmul, so flatten to [ph*pw*C, H] matching the host-side
    # patch extraction order (channel-last pixels within a patch).
    conv = _get(sd, p + "embeddings.patch_embedding.weight")
    Hd, C, ph, pw = conv.shape
    kernel = np.ascontiguousarray(
        conv.transpose(2, 3, 1, 0).reshape(ph * pw * C, Hd)
    )
    params: Params = {
        "patch_embed": {
            "kernel": cast(kernel),
            "bias": cast(_get(sd, p + "embeddings.patch_embedding.bias")),
        },
        "pos_embed": {
            # [P, H] learned table at base_grid**2 positions.
            "weight": cast(_get(sd, p + "embeddings.position_embedding.weight")),
        },
        "layers": {
            "norm1": ln("layer_norm1"),
            "norm2": ln("layer_norm2"),
            "q_proj": dense("self_attn.q_proj"),
            "k_proj": dense("self_attn.k_proj"),
            "v_proj": dense("self_attn.v_proj"),
            "o_proj": dense("self_attn.out_proj"),
            "fc1": dense("mlp.fc1"),
            "fc2": dense("mlp.fc2"),
        },
        "post_norm": {
            "weight": cast(_get(sd, p + "post_layernorm.weight")),
            "bias": cast(_get(sd, p + "post_layernorm.bias")),
        },
    }
    return params


def export_siglip(params: Params, cfg: VisionConfig) -> dict[str, np.ndarray]:
    """OryxViT pytree → HF SiglipVisionModel-layout state dict (fp32,
    `vision_model.`-prefixed) — inverse of import_siglip."""
    out: dict[str, np.ndarray] = {}
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))
    p = "vision_model."
    # [ph*pw*C, H] → Conv2d [H, C, ph, pw] (inverse of the import flatten).
    kern = f32(params["patch_embed"]["kernel"])
    ph = pw = cfg.patch_size
    C = cfg.num_channels
    out[p + "embeddings.patch_embedding.weight"] = np.ascontiguousarray(
        kern.reshape(ph, pw, C, -1).transpose(3, 2, 0, 1)
    )
    out[p + "embeddings.patch_embedding.bias"] = f32(
        params["patch_embed"]["bias"]
    )
    out[p + "embeddings.position_embedding.weight"] = f32(
        params["pos_embed"]["weight"]
    )
    out[p + "post_layernorm.weight"] = f32(params["post_norm"]["weight"])
    out[p + "post_layernorm.bias"] = f32(params["post_norm"]["bias"])
    lp = params["layers"]
    names = {
        "layer_norm1": ("norm1", _I), "layer_norm2": ("norm2", _I),
        "self_attn.q_proj": ("q_proj", _T), "self_attn.k_proj": ("k_proj", _T),
        "self_attn.v_proj": ("v_proj", _T),
        "self_attn.out_proj": ("o_proj", _T),
        "mlp.fc1": ("fc1", _T), "mlp.fc2": ("fc2", _T),
    }
    for hf_name, (key, post_kernel) in names.items():
        mod = lp[key]
        for leaf, arr in mod.items():
            post = post_kernel if leaf == "kernel" else _I
            suffix = "weight" if leaf in ("kernel", "weight") else "bias"
            stacked = f32(arr)
            for i in range(cfg.num_layers):
                out[f"{p}encoder.layers.{i}.{hf_name}.{suffix}"] = post(
                    stacked[i]
                )
    return out


def llm_hf_config(cfg: LLMConfig) -> dict[str, Any]:
    """HF config.json dict for an exported checkpoint.

    Qwen2 geometry (qkv biases) exports as Qwen2ForCausalLM; bias-free
    (Yi/Llama-class) geometry as LlamaForCausalLM with attention_bias
    false — HF's Qwen2 arch always expects qkv biases, so declaring it for
    a bias-free model would make from_pretrained fabricate random biases.
    """
    if cfg.attention_bias:
        arch: dict[str, Any] = {
            "architectures": ["Qwen2ForCausalLM"],
            "model_type": "qwen2",
        }
    else:
        arch = {
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama",
            "attention_bias": False,
            "mlp_bias": False,
        }
    return {
        **arch,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "hidden_act": "silu",
        "torch_dtype": "float32",
    }


def save_hf_checkpoint(params: Params, llm_cfg: LLMConfig,
                       vision_cfg: VisionConfig, directory: str) -> None:
    """Write a reference-layout checkpoint directory: LLM safetensors +
    config.json (HF Qwen2/Llama names), vision-tower safetensors (SigLIP
    names), and the compressor as a projector npz (the reference's
    `mm_projector.bin` analog) — the exporter half of SURVEY.md §5
    "Checkpoint / resume". Tokenizer files are NOT written (they belong to
    the source checkpoint; copy them alongside for HF `from_pretrained`).
    """
    from safetensors.numpy import save_file

    from oryx_tpu.utils import checkpoint as ckpt_lib

    os.makedirs(directory, exist_ok=True)
    save_file(
        export_qwen2(params["llm"], llm_cfg),
        os.path.join(directory, "model.safetensors"),
    )
    with open(os.path.join(directory, "config.json"), "w") as f:
        json.dump(llm_hf_config(llm_cfg), f, indent=2)
    save_file(
        export_siglip(params["vit"], vision_cfg),
        os.path.join(directory, "vision_tower.safetensors"),
    )
    ckpt_lib.save_projector_only(
        os.path.join(directory, "mm_projector"), params
    )


# ---------------------------------------------------------------------------
# LoRA adapter merge (PEFT layout)
# ---------------------------------------------------------------------------

# PEFT target-module name → our stacked-layer param key.
_LORA_TARGETS = {
    "q_proj": "q_proj", "k_proj": "k_proj", "v_proj": "v_proj",
    "o_proj": "o_proj", "gate_proj": "gate_proj", "up_proj": "up_proj",
    "down_proj": "down_proj",
}


def merge_lora(
    params: Params,
    adapter_sd: StateDict,
    cfg: LLMConfig,
    *,
    scaling: float,
) -> Params:
    # cfg validates adapter layer indices against the stacked param depth
    # (an out-of-range index would otherwise be an opaque numpy error).
    """Merge a PEFT LoRA adapter into full LLM weights: W += s·(B@A).

    The reference's builder merges `model_base` + LoRA checkpoints into one
    model (`load_pretrained_model(model_path, model_base, ...)`; SURVEY.md
    §2 "Model builder" LoRA-base merge path). Adapter keys look like
    `base_model.model.model.layers.{i}.self_attn.q_proj.lora_A.weight`
    (A: [r, in], B: [out, r], torch layout). Our kernels are [in, out], so
    the delta is A.T @ B.T. Returns a new params tree (llm subtree copied).
    """
    # Group adapter keys by (proj, layer).
    pat = re.compile(
        r"layers\.(\d+)\.(?:self_attn|mlp)\.(\w+)\.lora_(A|B)\.weight$"
    )
    found: dict[tuple[str, int], dict[str, np.ndarray]] = {}
    unhandled: list[str] = []
    for key in adapter_sd:
        m = pat.search(key)
        if not m:
            # Refuse rather than silently skip: modules_to_save full-weight
            # replacements, embedding/lm_head LoRA, DoRA magnitudes etc.
            # would otherwise merge to a model that quietly differs from
            # the reference merged model.
            unhandled.append(key)
            continue
        layer, proj, ab = int(m.group(1)), m.group(2), m.group(3)
        if proj not in _LORA_TARGETS:
            raise ValueError(f"unsupported LoRA target {proj!r} in {key}")
        if not 0 <= layer < cfg.num_layers:
            raise ValueError(
                f"adapter layer {layer} out of range for a "
                f"{cfg.num_layers}-layer model ({key})"
            )
        found.setdefault((proj, layer), {})[ab] = _get(adapter_sd, key)
    if unhandled:
        raise ValueError(
            "unsupported adapter weights (only decoder-proj lora_A/B "
            f"supported): {sorted(unhandled)[:5]}"
            f"{'...' if len(unhandled) > 5 else ''}"
        )
    if not found:
        raise ValueError("no LoRA weights found in adapter state dict")

    layers = dict(params["layers"])
    by_proj: dict[str, list[int]] = {}
    for proj, layer in found:
        by_proj.setdefault(proj, []).append(layer)
    for proj, idxs in by_proj.items():
        key = _LORA_TARGETS[proj]
        # np.array (copy): device-array views are read-only.
        kernel = np.array(jnp.asarray(layers[key]["kernel"], jnp.float32))
        for i in idxs:
            pair = found[(proj, i)]
            if set(pair) != {"A", "B"}:
                raise ValueError(f"layer {i} {proj}: incomplete LoRA pair")
            delta = (pair["A"].astype(np.float32).T
                     @ pair["B"].astype(np.float32).T) * scaling
            kernel[i] = kernel[i] + delta
        dtype = jnp.asarray(layers[key]["kernel"]).dtype
        layers[key] = {**layers[key], "kernel": jnp.asarray(kernel, dtype)}
    return {**params, "layers": layers}


def merge_lora_dir(params: Params, adapter_dir: str, cfg: LLMConfig) -> Params:
    """Merge a PEFT adapter directory (adapter_config.json +
    adapter_model.safetensors) into full LLM weights."""
    from safetensors.numpy import load_file

    with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
        acfg = json.load(f)
    from oryx_tpu.config import LoraConfig

    r = int(acfg["r"])
    # Scaling formula (incl. rsLoRA's alpha/sqrt(r)) lives on LoraConfig.
    scaling = LoraConfig(
        r=r,
        alpha=float(acfg.get("lora_alpha", r)),
        use_rslora=bool(acfg.get("use_rslora")),
    ).scaling
    sd_path = os.path.join(adapter_dir, "adapter_model.safetensors")
    return merge_lora(params, load_file(sd_path), cfg, scaling=scaling)


# PEFT module scope per decoder projection (single source with
# _LORA_TARGETS for what is adaptable at all).
_LORA_SCOPE = {
    "q_proj": "self_attn", "k_proj": "self_attn", "v_proj": "self_attn",
    "o_proj": "self_attn", "gate_proj": "mlp", "up_proj": "mlp",
    "down_proj": "mlp",
}


def export_lora(params: Params, lora) -> tuple[StateDict, dict]:
    """Trained in-tree adapters → PEFT layout (the reverse of merge_lora):
    per-layer `base_model.model.model.layers.{i}.<scope>.<proj>.lora_A/
    lora_B.weight` in torch [r, in]/[out, r] orientation, plus an
    adapter_config.json dict. `lora` is config.LoraConfig and must be the
    config the adapters were created with — r and scaling are validated
    against the params so the recorded adapter_config can never disagree
    with the weights (a silent factor-of-sqrt(r) merge error otherwise)."""
    sd: StateDict = {}
    targets = []
    for name, p in params["layers"].items():
        if not (isinstance(p, dict) and "lora_a" in p):
            continue
        targets.append(name)
        scope = _LORA_SCOPE[name]
        a = np.asarray(jnp.asarray(p["lora_a"], jnp.float32))  # [L, in, r]
        b = np.asarray(jnp.asarray(p["lora_b"], jnp.float32))  # [L, r, out]
        if a.shape[2] != lora.r:
            raise ValueError(
                f"{name}: adapter rank {a.shape[2]} != lora.r {lora.r}"
            )
        scale_leaf = float(np.asarray(p["lora_scale"]).flat[0])
        if abs(scale_leaf - lora.scaling) > 1e-6 * max(1.0, abs(scale_leaf)):
            raise ValueError(
                f"{name}: params lora_scale {scale_leaf} != config scaling "
                f"{lora.scaling} (r/alpha/use_rslora mismatch)"
            )
        for i in range(a.shape[0]):
            base = f"base_model.model.model.layers.{i}.{scope}.{name}"
            # ascontiguousarray: safetensors serializes the raw buffer, so
            # a transposed VIEW would be written with the wrong layout.
            sd[f"{base}.lora_A.weight"] = np.ascontiguousarray(a[i].T)
            sd[f"{base}.lora_B.weight"] = np.ascontiguousarray(b[i].T)
    if not sd:
        raise ValueError("params contain no LoRA adapters")
    adapter_cfg = {
        "peft_type": "LORA",
        "r": int(lora.r),
        "lora_alpha": float(lora.alpha),
        "use_rslora": bool(lora.use_rslora),
        "target_modules": sorted(targets),
        "bias": "none",
    }
    return sd, adapter_cfg


def export_lora_dir(params: Params, lora, out_dir: str) -> None:
    """Write a PEFT adapter directory (adapter_config.json +
    adapter_model.safetensors) loadable by merge_lora_dir / PEFT."""
    from safetensors.numpy import save_file

    sd, acfg = export_lora(params, lora)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "adapter_config.json"), "w") as f:
        json.dump(acfg, f, indent=2)
    save_file(sd, os.path.join(out_dir, "adapter_model.safetensors"))
