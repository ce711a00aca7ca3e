"""The per-layer metrics that read the device's time by the program's
own scope names (PR 58): the eight readers over a hand-made table, None
where there is nothing to read (no capture; a capture without a device
plane, which is the CPU rehearsal's; a program older than the
vocabulary, which is how the driver runs these files over the parent),
and the manifest's seventeen entries."""

import json
import os

import pytest

from benchmark import metric_files, scope_table
from test_bench_rehearsal_serve import ROOT

# A decode cell's slice: 2.0 s of `paged_decode_chunk`, 0.5 s of
# `paged_prefill`, 0.1 s of a program that names nothing.
DECODE = {
    "attn/mla": [0.30, 10], "attn/dsa_index": [0.10, 10],
    "attn/dsa_select": [0.20, 10], "attn/dsa_attend": [0.40, 10],
    "ffn/dense_ffn": [0.10, 10], "moe/moe_routed": [0.50, 10],
    "moe/moe_shared": [0.10, 10], "moe": [0.05, 10],
    "mixer/ssm_step": [0.04, 4],
    "embed": [0.01, 10], "head": [0.10, 10], "sample": [0.06, 10],
    "unscoped": [0.04, 30],
}
TABLE = {
    "paged_decode_chunk": DECODE,
    "paged_prefill": {"attn/mla": [0.20, 4], "moe/moe_routed": [0.25, 4],
                      "head": [0.04, 4], "unscoped": [0.01, 9]},
    "copy_pages": {"unscoped": [0.10, 3]},
}
TRAIN = {"train_step_fn": {
    "attn/attn_global": [1.0, 8], "ffn": [2.0, 8], "vision": [0.5, 2],
    "embed": [0.05, 2], "loss": [0.3, 2], "optimizer_update": [0.1, 2],
    "unscoped": [0.05, 40]}}
BLOCK = {"paged_block_step": {"attn": [1.0, 9], "moe": [3.0, 9]},
         "paged_decode_chunk": {"attn": [9.0, 9]}}
RUN = {"cell": "a-cell", "trace": {"busy_s": 2.5, "window_s": 3.0}}


@pytest.mark.parametrize("name,table,want", [
    ("scope.attn_share.batch", TABLE, 100 * 1.00 / 2.0),
    ("scope.attn_share", TABLE, 100 * 1.00 / 2.0),
    ("scope.attn_share.train", TRAIN, 100 * 1.0 / 4.0),
    # the FIRST step program the table holds: the block step
    ("scope.attn_share.batch", BLOCK, 25.0),
    ("scope.ffn_share.batch", TABLE, 100 * 0.10 / 2.0),
    ("scope.ffn_share.train", TRAIN, 50.0),
    ("scope.ffn_share.batch", BLOCK, None),  # no dense MLP there
    ("scope.moe_share.batch", TABLE, 100 * 0.65 / 2.0),
    ("scope.moe_share.batch", TRAIN, None),
    ("scope.mixer_share.batch", TABLE, 100 * 0.04 / 2.0),
    ("scope.mixer_share.batch", BLOCK, None),
    ("scope.head_share", TABLE, 100 * 0.17 / 2.0),
    ("scope.head_share.train", TRAIN, 100 * 0.35 / 4.0),
    ("scope.prefill_attn_share.batch", TABLE, 100 * 0.20 / 0.5),
    ("scope.prefill_attn_share", TRAIN, None),  # no prefill in the slice
    ("scope.dsa_share.batch", TABLE, 100 * 0.70 / 2.0),
    ("scope.dsa_share.batch", BLOCK, None),  # a program without an indexer
    # every program's unscoped seconds over the slice's busy seconds
    ("scope.unscoped_share.batch", TABLE, 100 * 0.15 / 2.5),
    ("scope.unscoped_share.train", TRAIN, 100 * 0.05 / 2.5),
    ("scope.unscoped_share", BLOCK, 0.0),
] + [(n, {}, None) for n in (  # the rehearsal, the parent
    "scope.attn_share", "scope.ffn_share", "scope.moe_share.batch",
    "scope.mixer_share.batch", "scope.head_share",
    "scope.prefill_attn_share", "scope.dsa_share.batch",
    "scope.unscoped_share")])
def test_a_reader_against_a_hand_made_table(monkeypatch, name, table, want):
    monkeypatch.setattr(scope_table, "table", lambda run: table)
    got = metric_files.load(name).read(RUN)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_a_step_programs_shares_add_up_with_its_unscoped_part(monkeypatch):
    monkeypatch.setattr(scope_table, "table", lambda run: TABLE)
    parts = [metric_files.load(n).read(RUN) for n in (
        "scope.attn_share", "scope.ffn_share", "scope.moe_share",
        "scope.mixer_share", "scope.head_share")]
    rest = 100.0 * DECODE["unscoped"][0] / 2.0
    assert sum(parts) + rest == pytest.approx(100.0)


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _bytes(fnum, payload):
    return _varint(fnum << 3 | 2) + _varint(len(payload)) + payload


def test_no_capture_and_a_capture_without_a_device_plane_read_as_nothing(
        monkeypatch, tmp_path):
    """What the CPU rehearsal leaves: a capture of host planes alone.
    The table is {} and every reader returns None."""
    monkeypatch.setattr(scope_table, "HERE", str(tmp_path))
    assert scope_table.table(RUN) == {}  # no directory at all
    d = tmp_path / "out" / RUN["cell"] / "trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    event = _varint(1 << 3) + _varint(7) + _varint(3 << 3) + _varint(5000)
    host = _bytes(2, b"/host:CPU") + _bytes(
        3, _bytes(2, b"python") + _bytes(4, event))
    (d / "vm.xplane.pb").write_bytes(_bytes(1, host))
    assert scope_table.table(RUN) == {}
    for e in ENTRIES:
        assert metric_files.load(e).read(RUN) is None


CHAT = ["oryx-7b.chat"]
TRAINING = ["oryx-7b-lora.sft-mixed", "oryx-7b-fsdp4.sft-mixed"]
ENTRIES = {  # name -> (layer, moves)
    "scope.attn_share": ("model step", "tpot_p90_ms"),
    "scope.attn_share.batch": ("model step", "serve_tok_s"),
    "scope.attn_share.train": ("model step", "train_tok_s"),
    "scope.ffn_share": ("model step", "tpot_p90_ms"),
    "scope.ffn_share.batch": ("model step", "serve_tok_s"),
    "scope.ffn_share.train": ("model step", "train_tok_s"),
    "scope.moe_share.batch": ("model step", "serve_tok_s"),
    "scope.mixer_share.batch": ("model step", "serve_tok_s"),
    "scope.head_share": ("model step", "tpot_p90_ms"),
    "scope.head_share.batch": ("model step", "serve_tok_s"),
    "scope.head_share.train": ("model step", "train_tok_s"),
    "scope.prefill_attn_share": ("model step", "tpot_p90_ms"),
    "scope.prefill_attn_share.batch": ("model step", "serve_tok_s"),
    "scope.dsa_share.batch": ("model step", "serve_tok_s"),
    "scope.unscoped_share": ("device", "tpot_p90_ms"),
    "scope.unscoped_share.batch": ("device", "serve_tok_s"),
    "scope.unscoped_share.train": ("device", "train_tok_s"),
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_entry_is_present_resolves_and_moves_a_metric_of_its_cells(
        manifest, name):
    (e,) = [e for e in manifest["per_layer"] if e["name"] == name]
    layer, moves = ENTRIES[name]
    reader = metric_files.load(name)
    assert callable(reader.read) and reader.LAYER == e["layer"] == layer
    assert (e["unit"], e["better"], e["source"], e["moves"]) == (
        "%", "lower", "device_trace", moves)
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e["workloads"] and set(e["workloads"]) <= cells
    assert set(e["workloads"]) <= set(e2e[moves]["workloads"])
    if not name.endswith((".batch", ".train")):
        assert e["workloads"] == CHAT
    if name.endswith(".train"):
        assert e["workloads"] == TRAINING
    if name.startswith(("scope.attn_share", "scope.head_share",
                        "scope.unscoped_share")):
        # every cell that reports the end-to-end metric it moves
        assert e["workloads"] == e2e[moves]["workloads"]
