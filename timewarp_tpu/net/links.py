"""The ``--link`` spec grammar — ONE parser for every surface.

The grammar used to live in :mod:`timewarp_tpu.cli` with the sweep
pack loader importing it back out of the CLI module — a layering smell
(library code pulling in argparse-land) and a drift hazard: a new link
kind added to one surface could silently not exist on the other. It
now lives here, next to the models it constructs (delays.py); the CLI
and :mod:`timewarp_tpu.sweep.spec` both import this module, so a solo
``--link`` string and a pack config's ``"link"`` field can never mean
different things.

Malformed specs die with a ``SystemExit`` naming :data:`LINK_GRAMMAR`
(never a raw IndexError/ValueError traceback — the loud-grammar
contract, tests/test_zgrammar.py); library callers that want an
exception catch the SystemExit and rewrap (sweep/spec.py
``RunConfig.parse_link``).
"""

from __future__ import annotations

__all__ = ["LINK_GRAMMAR", "parse_link"]

#: the --link grammar, named in every parse error
LINK_GRAMMAR = ("fixed:D | uniform:LO:HI | "
                "lognormal:MEDIAN:SIGMA[:FLOOR[:CAP]] | "
                "pareto:XM:ALPHA[:FLOOR[:CAP]] | "
                "drop:P:<inner> | quantize:Q:<inner> | never  "
                "(D/LO/HI/MEDIAN/XM/Q/FLOOR/CAP integer µs; "
                "P/SIGMA/ALPHA float; FLOOR and CAP clamp a sample, "
                "default 1 and 60000000, and FLOOR is the delay the "
                "link promises: what --window auto takes; "
                "never = drop probability 1, the old NeverConnected)")


def _clamp(parts, what: str) -> dict:
    """The optional ``[:FLOOR[:CAP]]`` tail of a float link's spec as
    the dataclass's keywords (absent: the class's own defaults)."""
    if not 3 <= len(parts) <= 5:
        raise ValueError(f"{what}, then optionally FLOOR and CAP")
    kw = dict(zip(("floor_us", "cap_us"), map(int, parts[3:])))
    floor = kw.get("floor_us", 1)
    if floor < 1:
        raise ValueError(f"FLOOR must be >= 1 µs, got {floor}")
    if "cap_us" in kw and kw["cap_us"] < floor:
        raise ValueError(
            f"CAP {kw['cap_us']} is under FLOOR {floor}")
    return kw


def parse_link(spec: str):
    """``fixed:D`` | ``uniform:LO:HI`` |
    ``lognormal:MEDIAN:SIGMA[:FLOOR[:CAP]]`` |
    ``pareto:XM:ALPHA[:FLOOR[:CAP]]`` (the two optional integers clamp
    a sample; left out, the classes' defaults) — optionally wrapped
    ``drop:P:<inner>`` and/or ``quantize:Q:<inner>``; ``never`` is the
    fully-severed link (``WithDrop(.., NEVER_CONNECTED)`` ≙ the reference's
    ``NeverConnected`` outcome). Malformed specs die with a message
    naming the grammar, never a raw IndexError/ValueError."""
    from .delays import (NEVER_CONNECTED, FixedDelay, LogNormalDelay,
                         ParetoDelay, Quantize, UniformDelay, WithDrop)
    parts = spec.split(":")
    kind = parts[0]
    try:
        if kind == "never":
            if len(parts) != 1:
                raise ValueError("never takes no parameters (every "
                                 "message is dropped)")
            return WithDrop(FixedDelay(1), NEVER_CONNECTED)
        if kind == "drop":
            if len(parts) < 3 or not parts[2]:
                raise ValueError("drop needs a probability and an "
                                 "inner spec")
            return WithDrop(parse_link(":".join(parts[2:])),
                            float(parts[1]))
        if kind == "quantize":
            if len(parts) < 3 or not parts[2]:
                raise ValueError("quantize needs a grid and an "
                                 "inner spec")
            return Quantize(parse_link(":".join(parts[2:])),
                            int(parts[1]))
        if kind == "fixed":
            if len(parts) != 2:
                raise ValueError("fixed takes exactly one delay")
            return FixedDelay(int(parts[1]))
        if kind == "uniform":
            if len(parts) != 3:
                raise ValueError("uniform takes exactly LO and HI")
            return UniformDelay(int(parts[1]), int(parts[2]))
        if kind == "lognormal":
            kw = _clamp(parts, "lognormal takes MEDIAN and SIGMA")
            return LogNormalDelay(int(parts[1]), float(parts[2]), **kw)
        if kind == "pareto":
            kw = _clamp(parts, "pareto takes XM and ALPHA")
            xm, alpha = int(parts[1]), float(parts[2])
            if xm < 1:
                raise ValueError(f"pareto XM must be >= 1 µs, got {xm}")
            if not alpha > 0:
                raise ValueError(
                    f"pareto ALPHA must be > 0, got {alpha}")
            return ParetoDelay(xm, alpha, **kw)
    except SystemExit:
        raise                   # an inner spec already produced the
    except (IndexError, ValueError) as e:        # grammar-named error
        raise SystemExit(
            f"malformed link spec {spec!r} ({e}); "
            f"grammar: {LINK_GRAMMAR}") from None
    raise SystemExit(
        f"unknown link spec kind {kind!r} in {spec!r}; "
        f"grammar: {LINK_GRAMMAR}")
