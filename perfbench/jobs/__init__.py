"""Job kinds a traffic mix can name under ``"job"``: one module each.

A job module gives ``make(cfg, traffic, n, edges)``, which returns a
callable that runs one job through the program's public entry point and
returns ``(answer, counters)``, and ``mismatches(answer, ref_phi,
traffic)``, the number of edges whose answer differs from the plain
reference's.
"""
