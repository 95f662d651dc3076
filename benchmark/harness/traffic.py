"""The one general traffic generator: a traffic file is a cycle.

``benchmark/traffic/<name>.json``::

    {"loop": "closed", "clients": 1, "deadline_ms": 900000,
     "warmup_cycles": 2, "trace_min_seconds": 3,
     "cycle": [{"template": "<name under templates/>",
                "constants": {"<placeholder>": {"draw": "<domain>"} |
                                                {"fixed": "<text>"}}}]}

A template's ``@placeholder@`` is replaced by the constant.  A drawn constant
walks the configuration's domain of that name (the generator returns them) in
an order shuffled from ``--seed``, reshuffled each time the domain is used up:
cycle *k* of a seed is the same texts whatever the run's length, every seed
sends the same mix in another order, and a domain no longer than
``warmup_cycles`` is warmed in full.  ``loop: open`` with ``rate`` and
``clients`` > 1 are parsed and refused at run time until a cell needs them
(see README.md).
"""

import re

import numpy as np

from . import data as files


class Traffic:
    def __init__(self, name: str, domains: dict, seed: int):
        spec = files.read_json("traffic", name + ".json")
        self.spec = spec
        self.loop = spec.get("loop", "closed")
        self.clients = int(spec.get("clients", 1))
        if self.loop != "closed" or self.clients != 1:
            raise NotImplementedError(
                f"traffic {name}: loop={self.loop!r} clients={self.clients} is "
                "parsed but not implemented (benchmark/README.md)"
            )
        self.deadline_ms = int(spec.get("deadline_ms", 900_000))
        self.warmup_cycles = int(spec.get("warmup_cycles", 2))
        self.trace_min_seconds = float(spec.get("trace_min_seconds", 3))
        self.steps = [(step["template"], files.template_text(step["template"]),
                       step.get("constants", {})) for step in spec["cycle"]]
        self.domains = domains
        self.seed = int(seed)
        self._orders = {}

    def _draw(self, step: int, domain: list, k: int, stream: int):
        """The constant that step ``step`` of cycle ``k`` takes."""
        epoch, pos = divmod(k, len(domain))
        at = (step, epoch, stream)
        if at not in self._orders:
            self._orders[at] = np.random.default_rng(
                [self.seed, step, epoch, stream]).permutation(len(domain))
        return domain[int(self._orders[at][pos])]

    def cycle(self, k: int, stream: str = "window"):
        """``[(template name, query text)]`` of cycle ``k``."""
        stream_no = {"window": 0, "warmup": 1}[stream]
        out = []
        for step, (name, text, constants) in enumerate(self.steps):
            # the placeholders of one step walk together
            for key, rule in sorted(constants.items()):
                if "fixed" in rule:
                    value = rule["fixed"]
                else:
                    value = self._draw(step, self.domains[rule["draw"]], k, stream_no)
                text = text.replace(f"@{key}@", value)
            if re.search(r"@\w+@", text):
                raise ValueError(f"template {name}: placeholder left in {text!r}")
            out.append((name, text))
        return out
