"""How far the hyper-connections' mixing maps of the last step were from
doubly stochastic: the largest ``|rowsum - 1|`` or ``|colsum - 1|`` over
tokens and sub-layers, which the step's carry holds (collection
``hc_stats``, published as the gauge ``hc.stochastic_err`` by
``horovod_tpu/models/hyper_connections.py:publish_stats``) and the
family's builder leaves under ``ran["hyper_connections"]``.  Rows are
divided last, so it reads the columns: some 1e-3 is what twenty rounds
leave on the worst of tens of thousands of maps, a value near 1 says the
iteration is not running.  A program without the counter: None."""


def read(run):
    counted = run["ran"].get("hyper_connections") or {}
    return counted.get("stochastic_err")
