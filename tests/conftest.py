import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests (the language-model dry-run cell in a "
        "512-device process) — deselected by default via addopts, run by "
        "the dedicated CI slow job with `-m slow`")
