"""Model families: what a cell needs of its architecture.

A configuration file names its ``family``; ``harness/spec.py`` imports
``families/<family>.py`` (or the package ``families/<family>/``) and
``run.py`` asks it for everything below. Nothing else under ``harness/``,
``reduce/`` or in ``run.py`` knows a family's keys, tasks, heads or program
classes. ``vilbert/`` is the family of both kept configurations;
``benchmark/tests/stub/families/stub.py`` is the smallest one that answers,
and the worked example. ``benchmark/README.md``, "A family", says what the
generic code promises in return.

A family is a module with these functions (``config`` and ``traffic`` are
the cell's files as this run uses them, rehearsal sizes and overrides
merged in):

  assets(config, traffic, cache_dir) -> (assets, {phase: seconds})
      Whatever has to be on disk before the schedule can be made, written
      under ``cache_dir`` once per checkout; ``assets`` is handed back to
      ``schedule``, ``boot`` and ``run_reference`` as it is.
  schedule(traffic, seed, seconds, assets) -> schedule
      ``harness/arrivals.schedule`` over the family's sessions: every
      request carries ``body``, ``key``, ``key_field``, ``rows``, ``kind``
      and whatever the family's check wants to find again.
  weights(config, seed) -> (params, parameter count)
      The served tree, on the device, from the seed, in the storage type the
      configuration states. ``harness/weights.make`` draws a whole float32
      tree in one call; a tree that does not fit beside a second copy of
      itself is drawn leaf by leaf in its own type.
  boot(config, traffic, params, assets, state_dir, rehearsal) -> (app, {phase: seconds})
      The running application, warmed for this traffic. The generic code
      asks of it ``http_port``, ``ws.bound_port`` and ``stop()``.
  units_since(app, since) -> float
      Units of work (the family's: image rows, tokens) dispatched since the
      monotonic time ``since``: the readers' ``units_in_trace``.
  flops_per_unit(config) -> int
      Matmul FLOPs of one unit: the readers' ``flops_per_unit``.
  unwritten_bytes(app, config) -> (bytes, {what to print of them})
      Device memory held reserved and never written to; ``written_share``
      leaves it out.
  sample(requests, stamps, seed, limit) -> picked
      Answered window requests to compare, drawn from the seed, spread over
      every ``kind``, the longest in it.
  run_reference(config, params, picked, assets, lower=None) -> outputs
      The plain reference over each picked request (``lower``: in that lower
      precision, for the control). Imports nothing of the program.
  compare(picked, stamps, outputs) -> {number: value}
      Every key of the cell's limits file, with ``compared`` (requests
      compared) and ``unanswered`` (sampled frames missing or malformed);
      further keys are printed and not judged. ``stamps[i]["result"]`` is
      the frame's ``result``.
  frame_of(request, output) -> result
      What a frame would say had ``output`` been served: how the control
      takes the program's place in ``compare``.
  check_traffic(config, traffic) -> None
      Raises where the traffic file cannot be run under the configuration
      (``tests/test_manifest.py`` asks it of every cell).

and a file ``tests/tiny.<family>.json``: ``config``, ``traffic`` and
``limits`` merged over the cell's files by ``--rehearsal``.
"""
