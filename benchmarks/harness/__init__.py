"""The benchmark's yardstick: everything here is general, nothing names a
cell, a configuration, a traffic mix or a metric.  Those are files found
by the names ``BENCHMARK.json`` gives (see ``manifest.py``)."""
