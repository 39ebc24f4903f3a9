"""What the benchmark takes from the program: the registry configuration a
cell names (checked against the configuration file's widths), and, for the
harness's own tests only, ways to break the serving engine underneath a
run."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

#: HF config key -> the program's ArchConfig field, for the width check
WIDTHS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "head_dim": "hd"}
FAULTS = ("token", "stale", "half")


def use_checkout(root: Path) -> None:
    """Import the program (``src/``) and its scripts from the checkout."""
    for p in (root / "scripts", root / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def program_config(config: dict):
    """The registry configuration the cell names. Every width must equal
    the configuration file's; ``program_overrides`` (the harness's tests
    only) replaces fields first."""
    from repro.configs import get_config
    cfg = get_config(config["registry_id"])
    if config.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **config["program_overrides"])
    c = config["config"]
    for key, field in WIDTHS.items():
        want = c.get(key) or (c["hidden_size"] // c["num_attention_heads"]
                              if key == "head_dim" else None)
        got = getattr(cfg, field)
        if int(got) != int(want):
            raise SystemExit(f"{config['registry_id']} runs {field}={got}, "
                             f"the configuration file says {key}={want}")
    return cfg


def break_engine(engine, fault: str) -> None:
    """Break the timed path of a ``ServingEngine`` (tests only):

    token  every fifth sampled token is replaced by the next id;
    stale  the decode step returns its cache unchanged;
    half   the decode step feeds the second half of the slots the first
           slot's token, as if half the batch were left out."""
    if not fault:
        return
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r} (have {FAULTS})")
    if fault == "token":
        orig, calls = engine._sample, [0]
        vocab = engine.cfg.vocab_size

        def sample(logits, req):
            calls[0] += 1
            tok = orig(logits, req)
            return (tok + 1) % vocab if calls[0] % 5 == 0 else tok
        engine._sample = sample
        return
    orig_decode = engine._decode
    if fault == "stale":
        engine._decode = lambda params, toks, cache: (
            orig_decode(params, toks, cache)[0], cache)
    else:
        def decode(params, toks, cache):
            half = toks.shape[0] // 2
            toks = toks.at[half:].set(toks[0])
            return orig_decode(params, toks, cache)
        engine._decode = decode
