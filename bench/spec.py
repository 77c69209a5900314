"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the root names each cell's configuration and traffic
mix and lists the metrics. Everything that belongs to one of them sits in
a file of its own under ``bench/``, found by that name alone, so a new
configuration, mix, metric or cell is a new file and never an edit:

- ``bench/configs/<config>.json``: the model's sizes as run, with its
  source; its ``reference`` names the plain reference module under
  ``bench/reference/``;
- ``bench/traffic/<traffic>.json``: the mix's parameters, read by
  :mod:`bench.generator`;
- ``bench/workloads/<cell>.json``: the engine's sizing for the cell and
  the limits its comparison is held to;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)``. A metric
  whose name ends in a cell-kind suffix (``step_mfu.serve``) falls back
  to the reader of its stem (``step_mfu.py``) when it has none of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

REPO = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A cell, file or metric that the benchmark cannot find or read."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: Callable


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    sizing: dict          # bench/workloads/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: Path

    def module(self, kind: str, name: str):
        """Import ``bench/<kind>/<name>.py`` under this cell's root."""
        return load_module(self.root / "bench" / kind / f"{name}.py")


def load_module(path: Path):
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


def _reader(root: Path, name: str) -> Callable:
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    return load_module(path).read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = REPO) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with all its files."""
    bench = _json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"no cell {name!r} in {root / 'BENCHMARK.json'}")
    w = entries[0]
    d = root / "bench"

    def metrics(kind):
        return [Metric(m["name"], m["unit"], _reader(root, m["name"]))
                for m in bench[kind] if _applies(m, name)]

    return Cell(name=name, config_name=w["config"], chips=int(w["chips"]),
                config=_json(d / "configs" / f"{w['config']}.json"),
                traffic=_json(d / "traffic" / f"{w['traffic']}.json"),
                sizing=_json(d / "workloads" / f"{name}.json"),
                end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"), root=root)


# Layer types as configuration files name them, and the program's mixers.
MIXERS = {"full_attention": "attn", "attention": "attn",
          "sliding_attention": "local", "mamba": "ssd"}


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for the cell's configuration file.

    Starts from the program's own registered config of the same
    architecture and sets every size from the file, so the file is the
    configuration as it is run. Each layer's mixer comes from the file's
    ``layer_types`` (or, without them, from its sliding window), its
    feed-forward from its expert count (``num_experts`` or
    ``num_local_experts``), with the expert sizes set in the program's own
    ``moe``. A ``program`` entry's execution settings follow; a nested one
    (``"moe": {...}``) replaces fields of the program's sub-config. The
    structure the file cannot express in sizes (norm, MLP form, tied
    embedding, every layer's kind, the experts' sizes) must agree with
    the program's, or the cell is refused.
    """
    from repro.configs import get_config
    c = cell.config
    prog = c["program"]
    base = get_config(prog["arch"])
    n = c["num_hidden_layers"]
    window = c.get("sliding_window")
    if "layer_types" in c:
        unknown = set(c["layer_types"]) - set(MIXERS)
        if unknown or len(c["layer_types"]) != n:
            raise SpecError(f"{cell.config_name}: layer_types must name "
                            f"{n} layers of {sorted(MIXERS)}")
        mixers = [MIXERS[t] for t in c["layer_types"]]
        if "local" not in mixers:
            window = None
    else:
        if not c.get("use_sliding_window", window is not None):
            window = None
        mixers = ["local" if window is not None else "attn"] * n
    n_experts = c.get("num_experts", c.get("num_local_experts"))
    sizes = dict(
        n_layers=n, d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]))
    experts = None
    if n_experts:
        ref = cell.module("reference", c["reference"])
        width = c[getattr(ref, "EXPERT_WIDTH", "moe_intermediate_size")]
        experts = (n_experts, c["num_experts_per_tok"], width)
        if base.moe is not None:
            sizes["moe"] = dataclasses.replace(
                base.moe, n_experts=n_experts, top_k=experts[1],
                d_ff_expert=width)
    # Execution settings of the program's own (format policy, dtypes,
    # capacity).
    for k, v in prog.items():
        if k == "arch":
            continue
        if isinstance(v, dict):
            sub = sizes.get(k, getattr(base, k, None))
            if not dataclasses.is_dataclass(sub):
                raise SpecError(f"{cell.config_name}: the program's "
                                f"{prog['arch']} has no {k} to set")
            v = dataclasses.replace(sub, **v)
        sizes[k] = v
    if window is not None:
        sizes["window"] = window
    cfg = dataclasses.replace(base, **sizes)
    want = {
        "tied_embeddings": bool(c.get("tie_word_embeddings", False)),
        "layer_kinds": tuple(
            (m, "moe" if n_experts else "mlp") for m in mixers),
        "experts": experts,
        "mlp_type": {"silu": "swiglu",
                     "gelu_pytorch_tanh": "gelu"}[c["hidden_act"]],
        "norm_type": "rmsnorm" if "rms_norm_eps" in c else "layernorm",
    }
    sparse = any(f == "moe" for _, f in cfg.layer_kinds)
    have = {"tied_embeddings": cfg.tied_embeddings,
            "layer_kinds": cfg.layer_kinds,
            "experts": (cfg.moe.n_experts, cfg.moe.top_k,
                        cfg.moe.d_ff_expert) if sparse else None,
            "mlp_type": cfg.mlp_type, "norm_type": cfg.norm_type}
    if want != have:
        diff = {k: (have[k], want[k]) for k in want if want[k] != have[k]}
        raise SpecError(f"{cell.config_name}: the program's {prog['arch']} "
                        f"and the configuration file differ in "
                        f"(program, file): {diff}")
    return cfg
