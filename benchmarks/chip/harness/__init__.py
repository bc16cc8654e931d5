"""The chip benchmark's yardstick: cell lookup, traffic generation, trace
reduction, FLOP and byte counts, the peak table and the runners that
drive the system under test (``repro``) through one cell."""
