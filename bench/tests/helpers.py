"""Shared pieces of the benchmark's CPU tests: the tiny configurations,
which only these tests let onto the CPU (the filtered one beside the plain
one, for mixes that filter), the tests' own filtered mix, and a cell built
from them."""
import json
import os

from bench.harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny-config.json")
TINY_FILTERED = os.path.join(HERE, "tiny-filtered-config.json")
BIG_SEED = 2 ** 31 + 12345
# mixes of the tests alone: no cell of the benchmark filters yet
TEST_MIXES = ("closed128-filtered",)


def tiny_cell(mix: str) -> spec.Cell:
    where = HERE if mix in TEST_MIXES else os.path.join(spec.BENCH, "traffic")
    with open(os.path.join(where, mix + ".json")) as f:
        tr = json.load(f)
    with open(TINY_FILTERED if "where_pool" in tr else TINY) as f:
        cfg = json.load(f)
    tr.update(max_batch=16, clients=32, warm_s=0.5, check_sample=64,
              pool=256)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tiny." + mix
    return spec.Cell(name=name, chips=1, config=cfg, traffic=tr,
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])
