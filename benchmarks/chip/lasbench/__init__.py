"""On-chip benchmark harness of the LASANA simulator.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
workload's configuration file; its traffic mix is
``traffic/<traffic>.json``, its correctness limits ``limits/<workload>.json``
and each per-layer metric's reader ``metrics/<metric>.py``, all beside this
package. See ``run.py`` for the command line.
"""
