"""A rehearsal of bench/run.py on the CPU at smoke size: the whole run
but the chip check, with no device metric printed; the check catches a
token altered where it is produced; the command refuses a CPU and a
checkout without the program."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.lib import result, spec
from repro.serve.scheduler import ReplicaEngine

FIX = Path(__file__).parent / "fixtures"
CELL = "smoke.smoke-chat"
SECONDS = 1.5


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    for d in ("configs", "traffic", "cells", "metrics"):
        (root / "bench" / d).mkdir(parents=True)
    shutil.copy(FIX / "smoke-config.json", root / "bench/configs/smoke.json")
    shutil.copy(FIX / "smoke-chat.json", root / "bench/traffic/smoke-chat.json")
    (root / "bench/cells" / f"{CELL}.json").write_text(json.dumps(
        {"rate_rps": 20.0,
         "limits": {"widest_logit_gap": 0.1, "widest_mask_gap": 0.1}}))
    for m in (spec.BENCH / "metrics").glob("*.py"):
        shutil.copy(m, root / "bench/metrics" / m.name)
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench["configs"] = [{"name": "smoke", "source": "fixture",
                         "file": "bench/configs/smoke.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "smoke",
                           "traffic": "smoke-chat", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return spec.find_cell(CELL, root=root, bench=bench)


@pytest.fixture(scope="module")
def untraced(cell):
    return result.run(cell, 2**31 + 11, SECONDS, False,
                      t_start=time.monotonic())


def test_rehearsal_reports_end_to_end_and_no_device_metric(cell, untraced):
    out = untraced
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 10
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert list(out)[-1] == "check"
    c = out["check"]
    assert c["widest_logit_gap"]["value"] <= c["widest_logit_gap"]["limit"]
    assert c["widest_mask_gap"]["value"] <= c["widest_mask_gap"]["limit"]
    for kind in ("greedy", "sampled"):
        n = c[f"{kind}_tokens"]
        assert n["value"] >= n["limit"] > 0


def test_traced_rehearsal_reads_host_metrics_only(cell):
    out = result.run(cell, 7, SECONDS, True, t_start=time.monotonic())
    device_sourced = {m["name"] for m in cell.per_layer
                      if m["source"] == "device_trace"}
    assert set(out["metrics"]) == {"queue_wait_p95_ms", "prefix_hit_rate",
                                   "kv_pool_peak_share", "step_ms"}
    assert not set(out["metrics"]) & device_sourced
    assert "busy_s" not in out["device"] and out["correct"] is True
    assert 0 < out["metrics"]["prefix_hit_rate"]["value"] < 100
    assert 0 < out["metrics"]["kv_pool_peak_share"]["value"] <= 100


def _alter_tokens(monkeypatch, vocab: int, greedy: bool) -> None:
    """Every new token of one kind of request is moved to its neighbour
    in the vocabulary where step_decode produces it."""
    step = ReplicaEngine.step_decode

    def altered(self, now):
        before = {rid: len(r.tokens) for rid, r in self._inflight.items()}
        n = step(self, now)
        for r in list(self._inflight.values()) + self.completed[-4:]:
            if r.sampling.greedy == greedy and \
                    len(r.tokens) > before.get(r.rid, 1e9):
                r.tokens[-1] = (r.tokens[-1] + 1) % vocab
        return n

    monkeypatch.setattr(ReplicaEngine, "step_decode", altered)


def test_a_token_altered_where_produced_is_not_correct(cell, monkeypatch):
    _alter_tokens(monkeypatch, cell.config["vocab_size"], greedy=True)
    out = result.run(cell, 2**31 + 11, SECONDS, False,
                     t_start=time.monotonic())
    assert out["correct"] is False
    c = out["check"]["widest_logit_gap"]
    assert c["value"] > 3 * c["limit"]


def test_a_sampled_token_altered_where_produced_is_not_correct(
        cell, monkeypatch):
    _alter_tokens(monkeypatch, cell.config["vocab_size"], greedy=False)
    out = result.run(cell, 2**31 + 11, SECONDS, False,
                     t_start=time.monotonic())
    assert out["correct"] is False
    c = out["check"]
    assert c["widest_mask_gap"]["value"] > 3 * c["widest_mask_gap"]["limit"]
    assert c["widest_logit_gap"]["value"] <= c["widest_logit_gap"]["limit"]


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.chat-sysprompt", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_refuses_a_machine_without_a_tpu():
    p = _run_cli(spec.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for d in spec.load_json(spec.ROOT / "BENCHMARK.json")["paths"]:
        shutil.copytree(spec.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
