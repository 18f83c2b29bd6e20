"""Process start to the first measured step (training) or the first due
request (serving): import, ``hvd.init``, weights made on the device,
compile or cache load, warm-up."""


def read(run):
    return run["setup_s"]
