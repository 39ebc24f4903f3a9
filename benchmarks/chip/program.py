"""What the benchmark takes from the program: the registry configuration a
cell names (checked against the widths that the configuration's
reference module names), and, for the
harness's own tests only, ways to break the serving engine underneath a
run."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

from suite import RunError

FAULTS = ("token", "stale", "half")


def use_checkout(root: Path) -> None:
    """Import the program (``src/``) and its scripts from the checkout."""
    for p in (root / "scripts", root / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def checked_widths(config: dict, reference) -> dict[str, tuple[str, int]]:
    """Published key -> (ArchConfig field, the width it must equal), for
    exactly the keys of the reference module's ``WIDTHS``, read from the
    configuration's ``config`` as its ``published`` completes it. A key
    that is not there, or not a whole number, is a ``RunError``."""
    c = reference.published(config["config"])
    name = config.get("name", config["registry_id"])
    out = {}
    for key, field in reference.WIDTHS.items():
        want = c.get(key)
        if want is None:
            raise RunError(f"configuration {name}: its config has no "
                           f"{key!r} to check {field} against")
        if not isinstance(want, int):
            raise RunError(f"configuration {name}: its config gives "
                           f"{key}={want!r}, not a whole number")
        out[key] = (field, want)
    return out


def program_config(config: dict, reference):
    """The registry configuration the cell names. Every width the
    configuration's reference module checks (``checked_widths``) must equal
    the program's; ``program_overrides`` (the harness's tests only)
    replaces fields first."""
    from repro.configs import get_config
    cfg = get_config(config["registry_id"])
    if config.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **config["program_overrides"])
    for key, (field, want) in checked_widths(config, reference).items():
        got = getattr(cfg, field, None)
        if got is None:
            raise RunError(f"{config['registry_id']} has no field {field!r} "
                           f"for the configuration file's {key}")
        if int(got) != want:
            raise RunError(f"{config['registry_id']} runs {field}={got}, "
                           f"the configuration file says {key}={want}")
    return cfg


def break_engine(engine, fault: str) -> None:
    """Break the timed path of a ``ServingEngine`` (tests only):

    token  every fifth sampled token is replaced by the next id;
    stale  the decode step returns its cache unchanged (a copy made before
           the call, which outlives a donated input);
    half   the decode step feeds the second half of the slots the first
           slot's token, as if half the batch were left out."""
    if not fault:
        return
    if fault not in FAULTS:
        raise RunError(f"unknown fault {fault!r} (have {FAULTS})")
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
        import jax
        import jax.numpy as jnp

        def decode(params, toks, cache):
            kept = jax.tree.map(jnp.copy, cache)
            return orig_decode(params, toks, cache)[0], kept
        engine._decode = decode
    else:
        def decode(params, toks, cache):
            half = toks.shape[0] // 2
            toks = toks.at[half:].set(toks[0])
            return orig_decode(params, toks, cache)
        engine._decode = decode
