from repro.train.assimilate import (
    AssimilationConfig,
    FitResult,
    SpikeDetector,
    fit_coefficient_field,
    forward_model,
    synthetic_observations,
    true_coefficients,
)
