"""Faults planted under the timed path, by name: ``FAULTS[name](job)`` breaks
the program (or the job's answers) for one run and leaves ``job._undo`` to
mend it. The tests drive each through the whole harness and see ``correct``
come out false; ``tools/readings.py --fault <name>`` reads one on the chip at
the cell's own size. Not used by the benchmark's own runs.
"""

import numpy as np


def _patched_fit(estimator, wrap):
    """``wrap(original)`` takes the place of the estimator's ``fit``."""
    def tamper(job):
        from sparkdq4ml_tpu import models

        cls = getattr(models, estimator)
        original = cls.fit
        cls.fit = wrap(original)
        job._undo = lambda: setattr(cls, "fit", original)
    return tamper


def half_fit(estimator):
    """Half of the batch left out: the fit's data pass sees the first half
    of the rows only, and solves for those."""
    def wrap(original):
        import jax.numpy as jnp

        def fit(self, frame, mesh=None):
            keep = jnp.arange(frame.num_slots) < frame.num_slots // 2
            return original(self, frame.filter(keep), mesh)
        return fit
    return _patched_fit(estimator, wrap)


def unfitted(estimator):
    """The solver returns its starting state: coefficients and intercept 0."""
    def wrap(original):
        def fit(self, frame, mesh=None):
            model = original(self, frame, mesh)
            zeros = np.zeros_like(np.asarray(model.coefficients))
            if hasattr(model, "_coefficients"):
                model._coefficients, model._intercept = zeros, 0.0
            else:
                model.coefficients, model.intercept = zeros, 0.0
            return model
        return fit
    return _patched_fit(estimator, wrap)


def half_rule(job):
    """Half of the batch left out in the DQ stage: the first rule marks the
    second half of the rows as bad, whatever their price."""
    import jax.numpy as jnp

    import sparkdq4ml_tpu as dq

    def rule(price):
        good = dq.minimum_price_rule(price)
        first = jnp.arange(good.shape[0]) < good.shape[0] // 2
        return jnp.where(first, good, -1.0)

    job.spark.udf.register("minimumPriceRule", rule, "double")


def half_score(model_class):
    """Half of the batch left out of the scoring pass: ``transform`` scores
    the first half of the rows, and the mean is taken over those."""
    def tamper(job):
        import jax.numpy as jnp

        from sparkdq4ml_tpu.models import regression

        cls = getattr(regression, model_class)
        original = cls.transform

        def transform(self, frame):
            keep = jnp.arange(frame.num_slots) < frame.num_slots // 2
            return original(self, frame.filter(keep))

        cls.transform = transform
        job._undo = lambda: setattr(cls, "transform", original)
    return tamper


def altered_answer(key):
    """An answer altered where it is produced: 5 % off."""
    def tamper(job):
        run = job.run

        def altered(stages):
            result = run(stages)
            result[key] = np.asarray(result[key]) * 1.05
            return result

        job.run = altered
    return tamper


FAULTS = {
    "higgs_fit": {
        "half_fit": half_fit("LogisticRegression"),
        "unfitted": unfitted("LogisticRegression"),
        "altered_coefficients": altered_answer("coefficients"),
        "altered_probe": altered_answer("probe_probability"),
    },
    "catering_dq_lasso": {
        "half_fit": half_fit("LinearRegression"),
        "half_rule": half_rule,
        "half_score": half_score("LinearRegressionModel"),
        "unfitted": unfitted("LinearRegression"),
        "altered_coefficient": altered_answer("coefficient"),
        "altered_rmse": altered_answer("rmse"),
    },
}
