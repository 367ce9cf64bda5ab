"""Plain float32 references, one module per model family (``bench/families``
names its own as ``reference``).

They import nothing of the program under test: each reads a configuration
file's keys and a parameter tree laid out as ``bench.weights`` makes it.
"""
