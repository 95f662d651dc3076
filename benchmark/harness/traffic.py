"""The one general traffic generator: a traffic file is a cycle.

``benchmark/traffic/<name>.json``::

    {"loop": "closed", "clients": 1, "deadline_ms": 900000,
     "warmup_cycles": 2, "warmup_ramp": [1], "trace_min_seconds": 3,
     "cycle": [{"template": "<name under templates/>",
                "constants": {"<placeholder>": {"draw": "<domain>"} |
                                                {"fixed": "<text>"}}}]}

A template's ``@placeholder@`` is replaced by the constant.  A drawn constant
walks the configuration's domain of that name (the generator returns them) in
an order shuffled from ``--seed``, reshuffled each time the domain is used up:
cycle *k* of a seed is the same texts whatever the run's length, every seed
sends the same mix in another order, and a domain no longer than
``warmup_cycles`` is warmed in full.

With ``clients`` *n* the window is *n* clients, each a closed loop of its
own: a client sends the cycle's steps in order, its next request when it has
read the reply to its last, and its next cycle when it has finished one; no
client waits for another, so how requests meet at the server is the server's
and the clients' doing, not the harness's.  Every client sends the same
number of cycles (``loadgen.free_run``); all clients' cycle *k* together are
round *k*.  Each client walks a share of the domain that is its own: the
values whose place in the domain is *c* modulo *n*, in the order they have
in the step's shuffled order, cycle *k* at position *k* of it.  So no two
clients ever send the same text, however far they drift apart (the batcher
folds equal texts into one execution, and the device-path count would come
out short), and with one client every text is what it was before there were
clients.  ``warmup_ramp`` is the list of client counts the warm-up goes
through, ``warmup_cycles`` cycles at each, the clients of a warm-up cycle
starting together, the last count being ``clients``; without the key it is
``[clients]``.  ``loop: open`` with
``rate`` is parsed and refused at run time until a cell needs it (see
README.md).
"""

import re

import numpy as np

from . import data as files


def ramp_of(name: str, spec: dict):
    """``(clients, warmup_ramp)`` of a traffic file; the ramp ends in
    ``clients`` and every count is at least 1."""
    clients = int(spec.get("clients", 1))
    ramp = [int(n) for n in spec.get("warmup_ramp", [clients])]
    if clients < 1 or min(ramp) < 1 or ramp[-1] != clients:
        raise ValueError(f"traffic {name}: warmup_ramp {ramp} does not end in "
                         f"clients {clients}, or a count is under 1")
    return clients, ramp


class Traffic:
    def __init__(self, name: str, domains: dict, seed: int):
        spec = files.read_json("traffic", name + ".json")
        self.spec = spec
        self.loop = spec.get("loop", "closed")
        if self.loop != "closed":
            raise NotImplementedError(
                f"traffic {name}: loop={self.loop!r} is parsed but not "
                "implemented (benchmark/README.md)"
            )
        self.clients, self.warmup_ramp = ramp_of(name, spec)
        self.deadline_ms = int(spec.get("deadline_ms", 900_000))
        self.warmup_cycles = int(spec.get("warmup_cycles", 2))
        self.trace_min_seconds = float(spec.get("trace_min_seconds", 3))
        self.steps = [(step["template"], files.template_text(step["template"]),
                       step.get("constants", {})) for step in spec["cycle"]]
        self.domains = domains
        self.seed = int(seed)
        self._orders = {}

    def warmup_counts(self):
        """The client count of each warm-up cycle, in the order sent."""
        return [n for n in self.warmup_ramp for _ in range(self.warmup_cycles)]

    def _draw(self, step: int, domain: list, client: int, k: int, stream: int):
        """The constant of client ``client`` in its cycle ``k``, step
        ``step``: position ``k`` of the client's own values in the shuffled
        order, reshuffled each time the client has used them up."""
        shares = min(self.clients, len(domain))
        mine = len(range(client % shares, len(domain), shares))
        epoch, pos = divmod(k, mine)
        at = (step, epoch, stream)
        if at not in self._orders:
            self._orders[at] = np.random.default_rng(
                [self.seed, step, epoch, stream]).permutation(len(domain))
        order = self._orders[at]
        return domain[int(order[order % shares == client % shares][pos])]

    def cycle(self, k: int, stream: str = "window", client: int = 0):
        """``[(template name, query text)]`` that client ``client`` sends in
        cycle ``k``."""
        stream_no = {"window": 0, "warmup": 1}[stream]
        out = []
        for step, (name, text, constants) in enumerate(self.steps):
            # the placeholders of one step walk together
            for key, rule in sorted(constants.items()):
                if "fixed" in rule:
                    value = rule["fixed"]
                else:
                    value = self._draw(step, self.domains[rule["draw"]],
                                       client, k, stream_no)
                text = text.replace(f"@{key}@", value)
            if re.search(r"@\w+@", text):
                raise ValueError(f"template {name}: placeholder left in {text!r}")
            out.append((name, text))
        return out
