"""The yardstick's shared parts: copies, so that a later PR which changes
``tools/`` or ``bench.py`` cannot move what the benchmark measures with."""
