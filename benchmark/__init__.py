"""The repo's benchmark: the yardstick lives here, the system under test
does not.  Nothing in this package imports ``bench.py`` or
``chip_smoke.py``; from the program it takes only ``dmlc_core_tpu``'s
public entry points and the evidence attributes they leave behind."""
