"""Tasks, one module per ``task`` named in a configuration file.

Each exposes ``build(config, mix, seed) -> bench.cell.Cell``.
"""
