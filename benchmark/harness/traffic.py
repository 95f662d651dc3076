"""The one general traffic generator: a traffic file is a cycle.

``benchmark/traffic/<name>.json``::

    {"loop": "closed", "clients": 1, "deadline_ms": 900000,
     "warmup_cycles": 2, "trace_min_seconds": 3,
     "cycle": [{"template": "<name under templates/>",
                "constants": {"<placeholder>": {"draw": "<domain>"} |
                                                {"fixed": "<text>"}},
                "repeat": 1}]}

A template's ``@placeholder@`` is replaced by the constant.  A drawn constant
comes from the configuration's domain of that name (the generator returns
them), anew for each request of each cycle, from a stream that depends only
on ``--seed``: cycle *k* of a seed is the same texts whatever the run's
length.  ``loop: open`` with ``rate`` and ``clients`` > 1 are parsed and
refused at run time until a cell needs them (see README.md).
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
        self.steps = []
        for step in spec["cycle"]:
            text = files.template_text(step["template"])
            for _ in range(int(step.get("repeat", 1))):
                self.steps.append((step["template"], text, step.get("constants", {})))
        self.domains = domains
        self.seed = int(seed)

    def cycle(self, k: int, stream: str = "window"):
        """``[(template name, query text)]`` of cycle ``k``."""
        rng = np.random.default_rng(
            [self.seed, k, {"window": 0, "warmup": 1}[stream]]
        )
        out = []
        for name, text, constants in self.steps:
            for key, rule in sorted(constants.items()):
                if "fixed" in rule:
                    value = rule["fixed"]
                else:
                    domain = self.domains[rule["draw"]]
                    value = domain[int(rng.integers(len(domain)))]
                text = text.replace(f"@{key}@", value)
            if re.search(r"@\w+@", text):
                raise ValueError(f"template {name}: placeholder left in {text!r}")
            out.append((name, text))
        return out
