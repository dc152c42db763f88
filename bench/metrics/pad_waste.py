"""Share of the window's computed client-steps that were padding: the
cohort assembly (``fed/cohort.py::build_cohorts``) pads each tier cohort's
client axis to a multiple of the plane's pad multiple (the chips of the
sharded plane) and each member to the cohort's most steps. Counted from
each round's tier assignment and the clients' batch counts by the rule in
``harness.cohort_layout``."""


def read(rec):
    if not rec["client_steps"]:
        return None
    return 100.0 * rec["padded_client_steps"] / rec["client_steps"]
