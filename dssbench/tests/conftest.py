def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: boots the server on the CPU backend (about a "
        "minute each); run with -m slow")
