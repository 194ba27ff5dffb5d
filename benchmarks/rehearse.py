"""CPU rehearsal of one cell at a tiny size: finds wrong paths, arguments and
control flow before any chip time is spent. NOT the benchmark command: it
prints no result line, its numbers mean nothing, and nothing may quote them.

    python3 benchmarks/rehearse.py --workload <name> [--seconds 3] [--trace 0|1]

The same manifest entry, configuration file, traffic file, runner and metric
readers as the real command; sizes are shrunk in memory only, to the family
file's `TINY_SIZES`. Four virtual CPU
devices stand in for a four-chip host; Pallas kernels fall to their jnp
references (the program's "auto" dispatch), so no kernel is rehearsed here.
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import json  # noqa: E402

import run as bench  # noqa: E402
from harness import load_module  # noqa: E402



def shrink(config, traffic) -> None:
    """Sizes from the table of the family the configuration names; the
    deployment and the traffic by the keys every file of their kind has."""
    family = load_module("families", config["family"])
    config["sizes"].update(family.TINY_SIZES)
    deployment = config["deployment"]
    if "num_kv_blocks" in deployment:
        deployment.update(num_kv_blocks=256, max_batch_size=8)
    for key, top in (("prompt_len", 48), ("output_len", 12)):
        if key in traffic:
            traffic[key] = {"median": top // 2, "sigma": 0.5, "min": 4,
                            "max": top}
    if traffic.get("shared_prefixes"):
        traffic["shared_prefixes"]["len"] = 64
    for key, value in (("warm_s", 1.0), ("ramp_s", 0.5), ("clients", 4),
                       ("global_batch", 2), ("seq", 64)):
        if key in traffic:
            traffic[key] = value


def main() -> None:
    args = bench.parse_args()
    result = bench.execute(args, rehearsal={"shrink": shrink})
    print("REHEARSAL (CPU, tiny sizes; not a result): "
          + json.dumps(result)[:2000], flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
