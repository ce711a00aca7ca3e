"""The benchmark's one seam to the program under test.

Everything that imports `oryx_tpu` or `jax` on behalf of the benchmark
goes through here: the configuration file -> OryxConfig, seeded weights
made on the device in ONE jitted call, the device record, the compile
cache. The load-generating parent never imports this module.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)["by_device_kind"]


class IdTokenizer:
    """Tokenizer stand-in (no checkpoint in a sealed checkout): one id
    per character in, `<id>` per token out — so a prompt of N characters
    is N tokens and a reply's text names its token ids exactly."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 50_000) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)



def device_record(chips: int, *, rehearse: bool) -> dict:
    """{"platform","kind","count"} as JAX reports it. Off a TPU, on a
    kind the peaks table lacks, or with another chip count than the
    cell asks for: SystemExit (no metric line is ever printed)."""
    import jax

    devs = jax.devices()
    rec = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if rehearse:
        return rec
    if rec["platform"] != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, found {rec}")
    if rec["kind"] not in load_peaks():
        raise SystemExit(
            f"benchmark: device kind {rec['kind']!r} is not in peaks.json"
        )
    if rec["count"] != chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chip(s), found {rec}"
        )
    return rec


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    does not report it, i.e. the CPU rehearsal)."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks))


def configure_cache() -> str:
    from oryx_tpu.utils.compile_cache import configure_compile_cache

    return configure_compile_cache()


def build_config(conf: dict):
    """Configuration file -> OryxConfig: the named preset (or the
    shipped recipe json) with only the file's `layout` overrides."""
    from oryx_tpu import config as cfg_lib

    layout = conf["layout"]
    if layout.get("recipe"):
        with open(os.path.join(ROOT, layout["recipe"])) as f:
            cfg = cfg_lib.OryxConfig.from_json(f.read())
    else:
        cfg = getattr(cfg_lib, layout["preset"])()
    llm = dataclasses.replace(cfg.llm, num_layers=layout["num_layers"])
    cfg = dataclasses.replace(
        cfg, llm=llm, dtype=layout["dtype"], attn_impl=layout["attn_impl"],
    )
    if "mesh" in layout:
        cfg = dataclasses.replace(
            cfg, mesh=cfg_lib.MeshConfig(**layout["mesh"])
        )
    if "train" in layout:
        train = layout["train"]
        t = cfg.train
        if "lora" in train:
            t = dataclasses.replace(t, tune="lora", lora=cfg_lib.LoraConfig(
                enable=True, **train["lora"]
            ))
        t = dataclasses.replace(t, **{
            k: v for k, v in train.items() if k != "lora"
        })
        cfg = dataclasses.replace(cfg, train=t)
    check_widths(conf, cfg)
    return cfg


# Configuration-file key (the source's own name) -> where the program
# keeps it. A file whose width the program would not run is refused.
_WIDTHS = {
    "hidden_size": ("llm", "hidden_size"),
    "intermediate_size": ("llm", "intermediate_size"),
    "num_attention_heads": ("llm", "num_heads"),
    "num_key_value_heads": ("llm", "num_kv_heads"),
    "vocab_size": ("llm", "vocab_size"),
    "head_dim": ("llm", "head_dim"),
}


def check_widths(conf: dict, cfg) -> None:
    if conf["layout"].get("preset") == "oryx_tiny":
        return
    for key, (group, attr) in _WIDTHS.items():
        want = conf.get(key)
        have = getattr(getattr(cfg, group), attr)
        if want is not None and want != have:
            raise SystemExit(
                f"config {conf.get('name')}: {key} {want} in the file, "
                f"{have} in the program"
            )


def seeded_params(cfg, seed: int, dtype_name: str):
    """All weights on the device from the seed, in ONE jitted program,
    in the dtype they are used in."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]

    @jax.jit
    def init(key):
        return oryx.init_params(cfg, key, dtype=dtype)

    params = init(jax.random.key(seed % (2**31 - 1)))
    jax.block_until_ready(params)
    return params
