"""Plain references, one per system, importing nothing of ``repro``."""
