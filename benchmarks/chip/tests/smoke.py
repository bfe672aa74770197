"""Cells of the benchmark at smoke size, for the CPU tests: the published
configuration files with their widths cut to the program's smoke configs,
and small traffic."""
import copy
import json
import os

import jax

from benchmarks.chip import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_WIDTHS = {
    "qwen2.5-3b": ({"hidden_size": 64, "num_hidden_layers": 3,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "intermediate_size": 160, "vocab_size": 512,
                    "rope_theta": 10000.0}, 16),
}
SMOKE_TRAFFIC = {
    "decode_heavy": {"batch": 4, "prompt_len": 16, "new_tokens": 12,
                     "cache_len": 32},
}
# Limits for smoke sizes, between what sound runs read on the CPU and what
# the control reads (five seeds each): serving gap, program 0 to 1.6e-3,
# fp8 control 2.2e-2 to 5.8e-2.
SMOKE_LIMITS = {
    "decode_heavy": {"logit_gap": 0.01},
}


def bench():
    return run.read_json(run.ROOT / "BENCHMARK.json")


def smoke_config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    widths, hd = SMOKE_WIDTHS[name]
    cfg.update(widths)
    cfg["program_fields"] = dict(cfg["program_fields"],
                                 resolved_head_dim={"value": hd})
    return cfg


def use_smoke_program(monkeypatch):
    """Makes the harness look up the program's smoke configs."""
    from repro import configs

    monkeypatch.setattr(configs, "get_config", configs.get_smoke_config)
    jax.config.update("jax_enable_compilation_cache", False)


def spec(cell):
    s = run.cell_spec(bench(), cell)
    traffic_name = s.traffic_name
    s.cfg_json = smoke_config(s.cfg_json["repro_config"])
    s.traffic = dict(copy.deepcopy(s.traffic), **SMOKE_TRAFFIC[traffic_name])
    s.limits = SMOKE_LIMITS[traffic_name]
    return s


def state(cell, seed=3):
    """A driver and its state for the cell at smoke size, not set up."""
    from repro import configs

    s = spec(cell)
    driver = run.load_module(run.HERE / "drivers"
                             / f"{s.traffic['driver']}.py")
    reference = run.load_module(run.HERE / "reference"
                                / f"{s.cfg_json['reference']}.py")
    st = driver.State(cfg_json=s.cfg_json, traffic=s.traffic, seed=seed,
                      reference=reference,
                      program_cfg=configs.get_smoke_config(
                          s.cfg_json["repro_config"]),
                      devices=jax.devices()[:s.chips])
    return driver, st, s
