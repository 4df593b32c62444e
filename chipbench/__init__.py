"""A chip benchmark for the paged serving path, driven by data.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once. A cell names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); its engine shape, fixed rate and correctness
limit are in ``cells/<cell>.json``; each metric is read by
``metrics/<metric>.py``. All of them are found by name.
"""
