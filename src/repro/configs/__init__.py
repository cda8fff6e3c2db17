"""Workload configs: ``hdiff`` is the paper's own COSMO hdiff grid."""
