"""Host clock around ``lgb.Dataset(...).construct()``: sampling, finding the
bins and binning every row."""


def read(run):
    return run.phases.get("bin")
