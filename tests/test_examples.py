"""Smoke tests: the tutorial examples run green, headless, as subprocesses.

Covers the round-5 tutorial-corpus additions (examples 17-21 — the
reference's ``policy/`` and ``real_scenario/`` walkthrough families plus
the saving-domain predictor).  Each example is its own process so its
``sys.path`` bootstrap, jax platform choice, and asserts run exactly as a
user would hit them; a non-zero exit or a failed in-example assert fails
the test.  Examples 01-16 exercise subsystems the rest of the suite
already covers in depth and several pay multi-minute mesh compiles, so
only the lightweight tutorial layer runs here.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES_DIR = Path(__file__).resolve().parent.parent / "examples"

TUTORIAL_EXAMPLES = [
    "17_policy_window.py",
    "18_smart_room_scenario.py",
    "19_fraud_detection_system.py",
    "20_mqtt_stream_bridge.py",
    "21_saving_predictor.py",
    "22_http_client.py",
    "23_real_dataset_lowlevel.py",
    "24_sparql_syntax_tour.py",
]


@pytest.mark.parametrize("name", TUTORIAL_EXAMPLES)
def test_example_runs_green(name):
    env = dict(os.environ)
    # examples 17-21 are host-only (no jax device work), but pin the CPU
    # platform anyway so a smoke run never claims a chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES_DIR / name)],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    assert proc.returncode == 0, (
        f"{name} failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}"
    )
    assert proc.stdout.strip(), f"{name} printed nothing"
