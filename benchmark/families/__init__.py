"""Family adapters: how a configuration becomes the program's own objects.

``build(config, traffic, devices, seed)`` returns a namespace with

``state, pipe, k``        the program's train state, its ``StepPipeline`` and
                          the steps one dispatch runs
``window``                the traffic: ``k`` batches on the device
``samples_per_step``      images or tokens one optimizer step trains
``flops_per_step``        model FLOPs of one step (``benchmark/flops.py``)
``first_dispatch()``      runs the first, drained dispatch(es) on the check
                          window, advances ``state`` and keeps what the
                          check needs
``check()``               after the window: the plain reference against what
                          ``first_dispatch`` kept; returns a dict with
                          ``correct`` and the numbers behind it

An adapter passes the program only what defines the work.  Every program
knob (steps per call, fused or Pallas paths, warm-up, block sizes) stays at
the checkout's default, so a PR that flips or deletes one shows in the cell.
"""
