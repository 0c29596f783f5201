#!/usr/bin/env python3
"""sha256 digests of the world a configuration file builds, so that a
change to ``lib/world.py`` can be shown to leave the cells that exist
unchanged.

    python3 perfbench/tools/world_digest.py [--root <checkout>]

For each configuration of BENCHMARK.json and each seed it prints one
line ``DIGEST {...}`` with four digests: the node records, the init
records with their placement, measured records 0-4,095, and every API
object built from them.  A record is dumped as the fields PR 25 gave it
(``POD_FIELDS``; what a later PR adds is empty for these files, which
the test asserts apart); an API object as ``dataclasses.asdict`` less
the two fields that differ from process to process (``uid``,
``creation_timestamp``).  Dumps are canonical JSON (sorted keys).

``tests/perfbench/test_perfbench_world.py`` holds the digests this
printed on the parent of PR 28, before that PR's edit.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = (1, 6, 2 ** 31 + 5)
MEASURED = 4096
POD_FIELDS = ("name", "cpu_milli", "mem_bytes", "priority", "labels",
              "features", "anti_required", "aff_required")


def _sha(obj) -> str:
    text = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()


def pod_fields(rec) -> dict:
    return {f: getattr(rec, f) for f in POD_FIELDS}


def api_fields(obj) -> dict:
    d = dataclasses.asdict(obj)
    for key in ("uid", "creation_timestamp"):
        del d["metadata"][key]
    return d


def digests(world, config: dict, seed: int) -> dict:
    nodes = world.node_records(config)
    init = world.init_records(config, seed)
    measured = [world.measured_record(config, "measured", i)
                for i in range(MEASURED)]
    api = ([api_fields(world.api_node(n)) for n in nodes]
           + [api_fields(world.api_pod(rec, node)) for rec, node in init]
           + [api_fields(world.api_pod(rec)) for rec in measured])
    return {"nodes": _sha([dataclasses.asdict(n) for n in nodes]),
            "init": _sha([[pod_fields(rec), node] for rec, node in init]),
            "measured": _sha([pod_fields(rec) for rec in measured]),
            "api": _sha(api)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=HERE,
                    help="the checkout whose perfbench/ and kubetpu/ build "
                         "the world")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    world = importlib.import_module("perfbench.lib.world")
    if not os.path.abspath(world.__file__).startswith(root):
        raise SystemExit(f"perfbench.lib.world came from {world.__file__}, "
                         f"not from {root}")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            config = json.load(f)
        for seed in SEEDS:
            print("DIGEST " + json.dumps(dict(
                {"config": entry["name"], "seed": seed},
                **digests(world, config, seed))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
