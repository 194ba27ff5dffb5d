"""Device, training cells: the same reading as `device_idle.serve`, over the
traced steps; a name of its own because it moves another end-to-end metric."""
from harness import load_module

read = load_module("layer_metrics", "device_idle.serve").read
