"""Traffic drivers. A traffic file names its driver (``"driver":
"forecast"``) and gives it parameters; the harness loads
``drivers/<driver>.py`` and calls its ``Driver(config, traffic, seed,
timed_path=None)``."""
