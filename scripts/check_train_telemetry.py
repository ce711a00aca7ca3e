"""CI well-formedness gate for the trainer telemetry exporter.

Runs a short (3-step, tiny-geometry) CPU train with `--metrics-port`
semantics (Trainer(metrics_port=0)) on a background thread and checks,
from OUTSIDE, what a Prometheus scraper + load balancer would see:

  * /readyz is 503 before the step loop starts and flips to 200 while
    it runs;
  * /metrics is the exact Prometheus content type, every family name
    carries the `oryx_train_` prefix (the shared `oryx_anomaly_` family
    is the one deliberate exception), no family is declared twice, and
    the acceptance series
    oryx_train_{loss,tokens_per_sec,mfu,goodput_ratio,hbm_live_bytes}
    are present with sane values;
  * /healthz answers 200.

Exit 0 = all good; nonzero prints what broke. Wired into
scripts/check_tier1.sh after the serving-endpoint gate.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REQUIRED = (
    "oryx_train_loss",
    "oryx_train_tokens_per_sec",
    "oryx_train_mfu",
    "oryx_train_goodput_ratio",
    "oryx_train_hbm_live_bytes",
)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _get(port: int, path: str, *, raw: bool = False):
    """(status, parsed body) — 503 is a result, not an exception."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            body = r.read().decode()
            return r.status, (body if raw else json.loads(body)), dict(
                r.headers
            )
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), dict(e.headers)


def main() -> None:
    import numpy as np

    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.train.trainer import Trainer

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tests.test_trainer_modes import _batch

    cfg = dataclasses.replace(
        cfg_lib.oryx_tiny(),
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4, tp=1, sp=1),
        train=dataclasses.replace(
            cfg_lib.oryx_tiny().train,
            num_train_steps=3, log_every=1, checkpoint_every=100,
            checkpoint_dir="/tmp/oryx_train_telemetry_gate_ckpt",
        ),
    )
    trainer = Trainer(cfg, metrics_port=0)
    port = trainer.telemetry.port
    code, body, _ = _get(port, "/readyz")
    if code != 503 or body.get("ready") is not False:
        fail(f"/readyz before the step loop: want 503/ready=false, got "
             f"{code} {body}")
    code, body, _ = _get(port, "/healthz")
    if code != 200 or body != {"status": "ok"}:
        fail(f"/healthz: want 200 ok, got {code} {body}")

    host = _batch(cfg)
    done = threading.Event()
    errors: list[BaseException] = []

    def run():
        try:
            trainer.fit(
                iter([host] * 3), num_steps=3, resume=False, prefetch=0
            )
        except BaseException as e:  # surfaced below
            errors.append(e)
        finally:
            done.set()

    threading.Thread(target=run, daemon=True).start()

    # /readyz must flip to 200 while the loop runs (the first step's
    # compile dominates; poll generously).
    deadline = time.monotonic() + 240
    flipped = False
    while time.monotonic() < deadline:
        code, body, _ = _get(port, "/readyz")
        if code == 200 and body.get("ready") is True:
            flipped = True
            break
        if done.is_set():
            break
        time.sleep(0.5)
    if errors:
        raise errors[0]
    if not flipped:
        fail("/readyz never flipped to 200 during the run")
    done.wait(timeout=240)

    code, text, headers = _get(port, "/metrics", raw=True)
    if code != 200:
        fail(f"/metrics returned {code}")
    if headers.get("Content-Type") != "text/plain; version=0.0.4":
        fail(f"/metrics content type {headers.get('Content-Type')!r}, "
             "want the Prometheus text exposition type")

    families: set[str] = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            name = line.split()[2]
            if name in families:
                fail(f"duplicate metric family {name!r}")
            families.add(name)
            continue
        if line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? (\S+)$", line)
        if not m:
            fail(f"malformed sample line: {line!r}")
        if not m.group(1).startswith(("oryx_train_", "oryx_anomaly_")):
            fail(f"unprefixed metric name: {line!r}")
    for want in REQUIRED:
        if want not in families:
            fail(f"required series {want} missing from /metrics "
                 f"(families: {sorted(f for f in families if 'train' in f)})")
    # 3 steps really happened and the accounting is sane.
    sample = {}
    for line in text.splitlines():
        if line and not line.startswith("#") and "{" not in line:
            k, v = line.rsplit(" ", 1)
            sample[k] = float(v)
    if sample.get("oryx_train_steps_total") != 3:
        fail(f"steps_total != 3: {sample.get('oryx_train_steps_total')}")
    if not np.isfinite(sample.get("oryx_train_loss", float("nan"))):
        fail(f"non-finite loss gauge: {sample.get('oryx_train_loss')}")
    if not 0 < sample.get("oryx_train_goodput_ratio", 0) <= 1:
        fail(f"goodput_ratio out of range: "
             f"{sample.get('oryx_train_goodput_ratio')}")

    trainer.close()
    code, _, _ = _get_or_dead(port)
    print("train telemetry OK: /readyz 503->200, /metrics "
          f"({len(families)} families, oryx_train_ prefixed, "
          "no duplicates, acceptance series present), /healthz 200")


def _get_or_dead(port: int):
    """After close() the exporter should stop answering; tolerate
    either a refused connection or a last in-flight response."""
    try:
        return _get(port, "/healthz")
    except OSError:
        return None, None, None


if __name__ == "__main__":
    main()
