"""A checkout with tiny cells added, for the CPU tests of the harness.

The cells ``tiny.warm``, ``tiny.cold`` and ``tiny.random`` explore Mamba-2
at the program's ``--reduced`` sizes with the committed traffic mixes cut to
a few dozen samples, so a whole run takes seconds on the CPU.  They are
added as files and entries; the committed ones are copied untouched.
"""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MODEL = {"kind": "mamba2", "num_hidden_layers": 2, "hidden_size": 64,
              "state_size": 16, "head_dim": 16, "expand": 2,
              "conv_kernel": 4, "vocab_size": 256,
              "tie_word_embeddings": True}


def make_tiny_root(dst: str, samples: int = 24, timeout_s: float = 30) -> str:
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        b = json.load(f)
    cfg = {"name": "tiny", "arch": "mamba2-780m", "program_reduced": True,
           "source": "test", "reduced": [], "model": TINY_MODEL,
           "workload": {"prompt_len": 16, "gen_tokens": 8, "batch": 1,
                        "dtype": "bfloat16"},
           "chips_per_board": 1, "limits": {"param_bytes_gap": 0.05}}
    with open(os.path.join(dst, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU test"})
    mixes = {"warm": "bayes-warm", "cold": "bayes-cold",
             "random": "random-cold"}
    traffic_of = {w["name"]: w["traffic"] for w in b["workloads"]}
    for kind, mix in mixes.items():
        with open(os.path.join(dst, "bench", "traffic", mix + ".json")) as f:
            t = json.load(f)
        t.update(samples=samples, timeout_s=timeout_s)
        with open(os.path.join(dst, "bench", "traffic",
                               f"tiny-{kind}.json"), "w") as f:
            json.dump(t, f)
        b["workloads"].append({"name": f"tiny.{kind}", "config": "tiny",
                               "traffic": f"tiny-{kind}", "chips": 1,
                               "why": "CPU test"})
    # a tiny cell reports the metrics of the committed cells on its mix
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            mine = {traffic_of[w] for w in m["workloads"]}
            m["workloads"] += [f"tiny.{kind}" for kind, mix in mixes.items()
                               if mix in mine]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return dst
