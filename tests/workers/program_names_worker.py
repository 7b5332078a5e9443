"""Subprocess worker: the XLA program names of a mesh-sharded engine.

Runs on a forced 4-host-device platform (set before jax import, so the
parent test process can stay single-device), builds paged overlapped
engines on a 2x2 ``("data", "model")`` mesh, with and without speculative
decoding, lowers every entry point and prints one JSON record:
``{"devices": 4, "engines": {label: {entry point: module name}}}``.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import dataclasses
import json
import re

import jax

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving import ContinuousEngine, SpecConfig


def main():
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              kv_tail=16)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((2, 2), ("data", "model"))
    rec = {"devices": jax.device_count(), "engines": {}}
    for label, spec in (("paged", None), ("paged-spec", SpecConfig(k=2))):
        eng = ContinuousEngine(params, cfg, slots=4, max_tokens=96,
                               prefill_chunk=16, paged=True, overlap=True,
                               mesh=mesh, spec=spec)
        rec["engines"][label] = {
            name: re.match(r"module @(\S+)",
                           fn.lower(*args).as_text()).group(1)
            for name, (fn, args) in eng.entry_points().items()}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
