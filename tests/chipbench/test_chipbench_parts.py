"""The benchmark's parts on the CPU: its counts against hand-computed
numbers, the peaks table, the traffic's repeatability, the names in
BENCHMARK.json, and the command's refusal to run without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, peaks
from chipbench.drivers.paged import Lengths
from chipbench.drivers.serve import wave_prompts
from chipbench.reference import paged as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
INTERNVL = _json("chipbench/configs/internvl2-2b.json")
STARCODER = _json("chipbench/configs/starcoder2-7b.json")


# ---------------------------------------------------------------- counts
def test_counts_internvl2_2b_by_hand():
    # attention 2048*128*(16+2*8) + 16*128*2048, SwiGLU 3*2048*8192, norms
    layer = 2048 * 128 * 32 + 16 * 128 * 2048 + 3 * 2048 * 8192 + 2 * 2048
    assert layer == 62918656
    p = counts.params(INTERNVL)
    assert p == {"layers": 24 * 62918656 + 2048, "embed": 189548544,
                 "unembed": 189548544}
    assert sum(p.values()) == 1889146880          # InternLM2-1.8B
    assert counts.kv_bytes_per_token(INTERNVL) == 98304
    # per token: 2 * (layer weights less norms) + 2 * V * d; attention
    # 4 * L * heads * head_dim per context position
    per_token = 2 * 1509949440 + 2 * 189548544
    assert counts.decode_flops(INTERNVL, [1025] * 32) == \
        32 * per_token + 196608 * 1025 * 32
    assert counts.prefill_flops(INTERNVL, 32, 1024) == pytest.approx(
        32 * (2 * 1509949440 * 1024 + 196608 * 1024 * 1025 // 2
              + 2 * 189548544))
    # weights (layers + head + looked-up rows) in bf16, K/V of 1025 tokens
    # for each of 32 sequences, slabs of 32 x 16 versions and 8 lanes
    want = ((1510049792 + 189548544 + 32 * 2048) * 2 + 98304 * 1025 * 32
            + 3 * 32 * 16 * 4 + 8 * 4)
    assert counts.decode_min_bytes(INTERNVL, [1025] * 32, 32, 16, 8) == want


def test_counts_starcoder2_7b_by_hand():
    # attention 4608*128*(36+2*4) + 36*128*4608, plain GELU MLP 2*4608*18432,
    # biases on q/k/v, o, up and down, two norms
    layer = (4608 * 128 * 44 + 36 * 128 * 4608 + 2 * 4608 * 18432
             + 128 * 44 + 4608 + 18432 + 4608 + 2 * 4608)
    assert layer == 217097728
    p = counts.params(STARCODER)
    assert p["layers"] == 16 * 217097728 + 4608
    assert sum(p.values()) == 3700060672          # one of two stages
    assert counts.kv_bytes_per_token(STARCODER) == 32768
    assert 12288 * 16 * counts.kv_bytes_per_token(STARCODER) == 6442450944


def test_counts_gc_kernels_by_hand():
    # 128 slabs of 8 int32 versions: 3 read, 3 written, freed, row mask
    assert counts.compact_bytes(128, 8) == 3 * 4096 + 3 * 4096 + 4096 + 512
    # 128 queries over 8 versions, 193-word rows (192 pages + length)
    assert counts.search_gather_bytes(128, 8, 193) == \
        2 * 128 * 8 * 4 + 2 * 128 * 193 * 4 + 2 * 128 * 4


# ----------------------------------------------------------------- peaks
def test_peaks_know_v5e():
    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_peaks_refuse_unknown_device(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup(kind)


# --------------------------------------------------------------- traffic
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench",
                                                       "traffic")))


@pytest.mark.parametrize("mix", MIXES)
def test_mix_repeats_for_a_seed(mix):
    m = _json(f"chipbench/traffic/{mix}.json")
    seed = 2 ** 33 + 12345
    if m["engine"] == "paged":
        runs = []
        for _ in range(2):
            ln = Lengths(m["num_seqs"], m["length_min"], m["length_max"],
                         seed)
            got = [ln.target.copy()]
            for _ in range(300):
                done = ln.advance(np.ones(m["num_seqs"], bool))
                ln.restart(done)
                got.append(ln.target.copy())
            runs.append(np.stack(got))
        np.testing.assert_array_equal(runs[0], runs[1])
        # another seed: the same requests, dealt to other sequences
        other = Lengths(m["num_seqs"], m["length_min"], m["length_max"], 7)
        first = Lengths(m["num_seqs"], m["length_min"], m["length_max"], seed)
        rows = sorted(map(tuple, np.column_stack([first.target,
                                                  first.sched])))
        assert rows == sorted(map(tuple, np.column_stack([other.target,
                                                          other.sched])))
        assert not np.array_equal(other.target, first.target)
    else:
        a = wave_prompts(seed, 1000, m["batch"], 8)
        b = wave_prompts(seed, 1000, m["batch"], 8)
        for _ in range(3):
            np.testing.assert_array_equal(next(a), next(b))


def test_kv_rows_are_a_function_of_seed_step_and_sequence():
    key = ref.traffic_key(2 ** 40 + 3)
    steps = jnp.array([5, 5, 9], jnp.int32)
    seqs = jnp.array([0, 3, 3], jnp.int32)
    k1, v1 = ref.kv_rows(key, steps, seqs, 2, 8, jnp.bfloat16)
    k2, v2 = ref.kv_rows(key, steps[::-1], seqs[::-1], 2, 8, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2)[::-1])
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2)[::-1])
    assert not np.array_equal(np.asarray(k1[0]), np.asarray(k1[1]))


def test_replay_lengths_follow_acks_and_resets():
    log = ref.TrafficLog(2)
    log.record(np.array([1, 1], bool), np.array([0, 0], bool))
    log.record(np.array([1, 0], bool), np.array([0, 1], bool))
    log.record(np.array([1, 1], bool), np.array([1, 0], bool))
    log.record(np.array([0, 1], bool), np.array([0, 0], bool))
    rp = ref.Replay(log)
    assert rp.lengths(1).tolist() == [2, 0]
    assert rp.lengths(2).tolist() == [0, 1]
    assert rp.lengths(3).tolist() == [0, 2]
    assert rp.token_steps(3, 1).tolist() == [2, 3]


# ----------------------------------------------------------- BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_names_resolve_to_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    confs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in confs
        mix = _json(f"chipbench/traffic/{w['traffic']}.json")
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", f"{mix['engine']}.py"))
        assert len(w["why"]) <= 200
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n)
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics",
                                           f"{n}.py"))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


# ----------------------------------------------------------------- command
def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
