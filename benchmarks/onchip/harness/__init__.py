"""The on-chip benchmark's harness: what every cell shares.

Everything that belongs to one configuration, traffic mix, payload kind,
reference model or per-layer metric lives in a file of its own beside this
package, found by the name that ``BENCHMARK.json`` gives it.
"""
