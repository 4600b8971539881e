"""Every cell of BENCHMARK.json loads by name with its configuration,
traffic and metric files; the traffic generator is deterministic per seed;
the device check refuses what is not a listed TPU."""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import cells, spec as spec_lib, traffic
from bench import run

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_with_its_files(name):
    c = cells.load_cell(name)
    assert c.chips in (1, 4)
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m.moves in names, (m.name, m.moves)
    s = spec_lib.from_config(c.config)
    # no width of the published model is cut
    assert s.d_model == c.config["hidden_size"]
    assert s.experts_held >= s.experts
    assert set(c.config["reduced"]) <= {"num_hidden_layers"}


def test_benchmark_names_and_keys():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in BENCH[section]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    for c in BENCH["configs"]:
        assert cells.load_config(c["name"])["source"] == c["source"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_missing_reader_fails(tmp_path):
    with pytest.raises(cells.CellError):
        cells.metric_reader("no_such_metric", root=str(tmp_path))


MIXES = sorted(os.path.basename(f)[:-5] for f in glob.glob(
    os.path.join(cells.BENCH_DIR, "traffic", "*.json")))


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_deterministic_per_seed(mix):
    m = cells.load_traffic(mix)
    seed = 2**31 + 12345
    a = traffic.generate(m, seed, 1000)
    b = traffic.generate(m, seed, 1000)
    c = traffic.generate(m, seed + 1, 1000)
    flat = lambda t: t.warm + t.fill + t.queue
    for x, y in zip(flat(a), flat(b)):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    # another seed: the same sizes in the same order, other tokens
    sizes = lambda t: [(len(s.prompt), s.max_new_tokens) for s in flat(t)]
    assert sizes(a) == sizes(c)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(flat(a), flat(c)))
    print(mix, json.dumps(traffic.describe(a)))


def test_chat_shares_system_prompts():
    m = cells.load_traffic("chat-sessions")
    t = traffic.generate(m, 7, 1000)
    specs = t.queue
    heads = collections.Counter(s.prompt[:16].tobytes() for s in specs)
    assert len(heads) <= m["system_prompts"]["count"]
    assert heads.most_common(1)[0][1] > len(specs) / 8
    assert all(len(s.prompt) + s.max_new_tokens <= m["max_total"]
               for s in specs)


def test_system_prompts_are_warmed():
    m = cells.load_traffic("chat-sessions")
    t = traffic.generate(m, 7, 1000)
    assert len(t.warm) == m["system_prompts"]["count"]
    cached = {s.prompt.tobytes() for s in t.warm}
    assert all(any(q.prompt[:len(w.prompt)].tobytes() == w.prompt.tobytes()
                   for w in t.warm) for q in t.queue)
    assert len(cached) == len(t.warm)
    assert traffic.generate(cells.load_traffic("reasoning"), 7, 1000).warm == []


@pytest.mark.parametrize("mix", ["chat-sessions", "reasoning"])
def test_closed_loop_fill_is_staggered(mix):
    m = cells.load_traffic(mix)
    t = traffic.generate(m, 7, 1000)
    assert len(t.fill) == m["clients"]
    assert len(t.queue) == m["block"] * m["blocks"]
    left = sorted(s.max_new_tokens for s in t.fill)
    assert left[0] < m["output"]["median"] < left[-1]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.NoDevice):
        run.load_peaks("TPU v99")
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_run_refuses_the_cpu_before_building():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "bench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
