"""Tiny stand-ins for the benchmark's cells, small enough for the CPU.

Each keeps the cell's configuration and workload files and overrides only
sizes: the FL cell runs a two-stage ResNet of width 8 over 3 clients, the
serving cells the program's smoke variant of yi-6b (2 layers, d_model 64,
vocabulary 512).  ``run_tiny`` drives a whole run through
``chipbench.run.execute``, skipping only the look for a chip.
"""

from chipbench import registry, run

#: Any whole number up to a little over 2**31 is a seed.
SEED = 2 ** 31 + 11

FL_SIZES = dict(depth_blocks=[1, 1], width=8, image_hw=16, n_clients=3,
                local_batch=4)
SERVE_SIZES = dict(smoke=True, num_hidden_layers=2, hidden_size=64,
                   num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                   intermediate_size=128, vocab_size=512, rope_theta=1e4)
SERVE_LOAD = dict(slots=4, prompt_len=16, max_new=8, s_max=32, requests=12,
                  steps_per_s=5, check_requests=3)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(cell: str) -> tuple:
    """(workload, config, config module) of ``cell`` at CPU sizes."""
    wl = registry.workload(cell)
    cfg = registry.config(wl["config"])
    if cfg["kind"] == "fl":
        cfg = dict(cfg, **FL_SIZES)
        wl = dict(wl, rounds_per_s=1.0, cohort_sizes=[FL_SIZES["n_clients"]])
    else:
        cfg = dict(cfg, **SERVE_SIZES)
        wl = dict(wl, **SERVE_LOAD)
    return wl, cfg, registry.config_module(wl["config"])


def run_tiny(cell: str, seconds: float = 5, trace: bool = False,
             seed: int = SEED) -> tuple:
    wl, cfg, mod = tiny(cell)
    return run.execute(wl, cfg, mod, seed, seconds, trace,
                       registry.peaks("TPU v5 lite"), CPU)
