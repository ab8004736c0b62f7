"""Shared pieces of the benchmark's CPU tests: the tiny configuration,
which only these tests let onto the CPU, and a cell built from it."""
import json
import os

from bench.harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny-config.json")
BIG_SEED = 2 ** 31 + 12345


def tiny_cell(mix: str) -> spec.Cell:
    with open(TINY) as f:
        cfg = json.load(f)
    with open(os.path.join(spec.BENCH, "traffic", mix + ".json")) as f:
        tr = json.load(f)
    tr.update(max_batch=16, clients=32, warm_s=0.5, check_sample=64,
              pool=256)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tiny." + mix
    return spec.Cell(name=name, chips=1, config=cfg, traffic=tr,
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])
