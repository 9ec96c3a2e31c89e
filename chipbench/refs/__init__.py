"""Plain host references of the sketches, independent of ``src/repro``."""
