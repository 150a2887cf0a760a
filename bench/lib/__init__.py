"""The chip benchmark's harness: plans, load, capture, reference, checks.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in its own data or reader file under ``bench/``; the modules
here read those files by name and hold no cell-specific code.
"""
