"""Aux-subsystem tests: profiling annotations, metric logger, launch
config files, train-CLI arg surface (SURVEY.md §5)."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_annotation_smoke():
    import jax.numpy as jnp

    from oryx_tpu.utils import profiling

    billed = []
    clock = profiling.PhaseClock(
        "oryx.test", lambda name, s: billed.append(name), base="rest"
    )
    with clock.phase("unit-test-region"):
        x = jnp.ones((4,)) + 1
    assert float(x.sum()) == 8.0
    assert billed == ["rest", "unit-test-region"]


def test_metric_logger_writes_jsonl(tmp_path):
    from oryx_tpu.utils.metrics import MetricLogger

    path = str(tmp_path / "m.jsonl")
    lg = MetricLogger(path, log_every=2)
    lg.log_step(1, {"loss": 1.0, "num_tokens": 10})
    lg.log_step(2, {"loss": 0.5, "num_tokens": 10})
    lg.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 1 and lines[0]["step"] == 2
    assert "tokens_per_sec_per_chip" in lines[0]


def test_metric_logger_tensorboard(tmp_path):
    from oryx_tpu.utils.metrics import MetricLogger

    tb_dir = str(tmp_path / "tb")
    lg = MetricLogger(None, log_every=1, tensorboard_dir=tb_dir)
    if lg._tb is None:
        pytest.skip("tensorboard writer unavailable")
    lg.log_step(1, {"loss": 1.0, "num_tokens": 10})
    lg.close()
    assert any(
        f.startswith("events.out.tfevents") for f in os.listdir(tb_dir)
    )


@pytest.mark.parametrize("name", [
    "oryx_7b_sft", "oryx_34b_sft", "oryx_7b_longvideo", "oryx_7b_pretrain",
    "oryx_1_5_32b_sft", "oryx_7b_sft_lora", "oryx_34b_longvideo",
])
def test_launch_configs_load(name):
    from oryx_tpu.config import OryxConfig

    with open(os.path.join(REPO, "scripts", "configs", f"{name}.json")) as f:
        cfg = OryxConfig.from_json(f.read())
    assert cfg.mesh.num_devices >= 4
    # Sequence-parallel meshes train under ring attention ("ring" = xla
    # inner loop, "ring_flash" = Pallas inner — the 32B/34B pod
    # recipe); dense meshes name the Pallas kernel. (Neither has run on
    # the current chip: its compiler refuses a Mosaic kernel under a
    # mesh until the call is wrapped in a shard_map — ROADMAP S8.)
    if cfg.mesh.sp > 1:
        assert cfg.attn_impl.startswith("ring")
    else:
        assert cfg.attn_impl == "pallas"


def test_train_cli_argparser():
    from oryx_tpu.train.cli import build_argparser

    ap = build_argparser()
    args = ap.parse_args([
        "--config", "c.json", "--data", "d.json",
        "--tokenizer-path", "tok", "--num-steps", "5",
    ])
    assert args.sharding == "fsdp" and args.num_steps == 5


def test_train_cli_end_to_end(tmp_path, monkeypatch):
    """The SFT entry point runs a step on real (tiny, synthetic) data
    and exports a LOADABLE weights-only model dir — the exported tree
    must not drag the optimizer moments along (2/3 of a TrainState)."""
    import json

    import numpy as np
    from PIL import Image

    import dataclasses

    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.serve import builder
    from oryx_tpu.train import cli as train_cli

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")

    class FakeTok:
        def encode(self, text, add_special_tokens=False):
            return [min(ord(c), 500) for c in text]

        def decode(self, ids, skip_special_tokens=True):
            return "".join(chr(i) for i in ids if 0 < i < 500)

    import transformers

    monkeypatch.setattr(
        transformers.AutoTokenizer, "from_pretrained",
        staticmethod(lambda *a, **k: FakeTok()),
    )

    cfg = cfg_lib.oryx_tiny()
    cfg = dataclasses.replace(
        cfg,
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4),
        train=dataclasses.replace(
            cfg.train, global_batch_size=8, num_train_steps=1,
            checkpoint_dir=str(tmp_path / "ckpt"), log_every=1,
        ),
    )
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(cfg.to_json())

    img = tmp_path / "img.png"
    Image.fromarray(
        np.random.default_rng(0).integers(0, 255, (28, 28, 3), dtype=np.uint8)
    ).save(img)
    records = [
        {"id": i, "image": img.name, "conversations": [
            {"from": "human", "value": "<image>\nwhat?"},
            {"from": "gpt", "value": "thing"},
        ]}
        for i in range(8)
    ]
    data_path = tmp_path / "data.json"
    data_path.write_text(json.dumps(records))
    out_dir = tmp_path / "model"

    train_cli.main([
        "--config", str(cfg_path), "--data", str(data_path),
        "--media-root", str(tmp_path), "--tokenizer-path", "unused",
        "--output-dir", str(out_dir), "--num-steps", "1",
    ])

    _, params, cfg2 = builder.load_pretrained_model(
        str(out_dir), tokenizer=FakeTok()
    )
    assert cfg2.llm == cfg.llm
    # Weights-only export: model subtrees, no TrainState wrapper.
    assert set(params) == {"llm", "vit", "compressor"}
