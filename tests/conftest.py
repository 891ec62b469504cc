import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test")


def pytest_addoption(parser):
    parser.addoption("--skip-slow", action="store_true", default=False,
                     help="skip tests marked slow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--skip-slow"):
        skip = pytest.mark.skip(reason="--skip-slow")
        for item in items:
            if "slow" in item.keywords:
                item.add_marker(skip)
