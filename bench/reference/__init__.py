"""Plain float32 reference of the dense decoder family the benchmark serves.

It imports nothing of the program under test: it reads a configuration
file's keys and a parameter tree laid out as ``bench.weights`` makes it.
"""
