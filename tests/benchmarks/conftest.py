"""Shared by the benchmark's tests: the repo root on ``sys.path`` (the
benchmark is a package, ``benchmarks``) and the tiny configuration."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def tiny_config():
    """gpt2-medium's file with its rehearsal sizes applied."""
    from benchmarks.lib import harness
    config = harness.load_json(ROOT, "benchmarks", "configs",
                               "gpt2-medium.json")
    return dict(config, token_id_limit=config["rehearse_token_id_limit"],
                gpt_config={**config["gpt_config"],
                            **config["rehearse_gpt_config"]})
