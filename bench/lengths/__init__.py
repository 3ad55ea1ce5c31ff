"""Length distributions of prompts and answers, one file per ``dist`` a
traffic mix names.  Each defines ``quantile(spec, q)``: the inverse CDF
at the probabilities ``q`` (the generator rounds and clips to the spec's
``min`` and ``max``)."""
