"""Launchers: production mesh, multi-pod dry-run, train/serve/tune drivers
and roofline analysis.

NOTE: ``dryrun`` sets ``XLA_FLAGS`` for 512 placeholder devices at import
time — import it only in a dedicated process, never from tests.
"""
