"""On-chip benchmark harness of the LASANA simulator.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
workload's configuration file; its traffic mix is
``traffic/<traffic>.json`` (whose driver, unless built in, is
``drivers/<driver>.py``), its correctness limits ``limits/<workload>.json``
and each per-layer metric's reader ``metrics/<metric>.py``, all beside this
package. A configuration states its circuit graph, its surrogates per
circuit kind and its plain reference (``model.py``). See ``run.py`` for
the command line.
"""
