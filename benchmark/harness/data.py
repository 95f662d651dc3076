"""Finding a cell's files by name, and turning generated columns into
N-Triples chunks under the server's request limit."""

import importlib
import json
import os

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
LOAD_CHUNK_BYTES = 48 * 1024 * 1024  # under the server's 64 MiB request limit


def path(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def read_json(*parts: str) -> dict:
    with open(path(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module, found by name."""
    if not os.path.exists(path(folder, name + ".py")):
        raise FileNotFoundError(path(folder, name + ".py"))
    return importlib.import_module(f"benchmark.{folder}.{name}")


def template_text(name: str) -> str:
    with open(path("templates", name + ".rq"), encoding="utf-8") as f:
        return f.read().strip()


def ntriples_chunks(data: dict):
    """The data set as N-Triples texts, each under the request limit."""
    terms = np.array(data["terms"], dtype=object)
    s, p, o = data["s"], data["p"], data["o"]
    chunks, cur, cur_bytes, step = [], [], 0, 100_000
    for i in range(0, len(s), step):
        j = slice(i, i + step)
        text = "".join(terms[s[j]] + " " + terms[p[j]] + " " + terms[o[j]] + " .\n")
        if cur and cur_bytes + len(text) > LOAD_CHUNK_BYTES:
            chunks.append("".join(cur))
            cur, cur_bytes = [], 0
        cur.append(text)
        cur_bytes += len(text)
    if cur:
        chunks.append("".join(cur))
    return chunks
