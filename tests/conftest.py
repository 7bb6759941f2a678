"""Test-tier configuration: fast by default, opt into the slow tier.

Tier-1 (`PYTHONPATH=src python -m pytest -x -q`) must stay green on CPU.
Cases that take minutes there (long pipeline/theory/distributed runs) are
marked ``slow`` and deselected unless ``--runslow`` is given; a case of a
few seconds stays in tier-1.
"""
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (multi-minute pipeline/theory cases)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running case, deselected unless --runslow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow tier: pass --runslow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
