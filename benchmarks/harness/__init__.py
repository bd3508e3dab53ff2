"""The benchmark's own code: everything the yardstick owns lives under
``benchmarks/`` and imports the program only where it builds the system
under test (``models/*.py`` ``build_*`` functions, the two cell runners)."""
