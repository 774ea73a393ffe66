#!/usr/bin/env python3
"""Writes ``small.xplane.pb`` beside itself: a two-chip trace of 10 ms with
figures that can be checked by hand (see ``test_trace_reduce.py``).  Plane
and line names are the ones a TPU v5e trace carries.  Times in the text are
picoseconds from each line's ``timestamp_ns``."""

import os

from jax.profiler import ProfileData

MS = 1_000_000_000  # picoseconds

TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0          duration_ps: %(ms4)d }
    events { metadata_id: 2 offset_ps: %(ms1)d    duration_ps: %(ms1)d }
    events { metadata_id: 2 offset_ps: %(ms5)d    duration_ps: %(ms2)d }
    events { metadata_id: 3 offset_ps: %(ms9)d    duration_ps: %(us50)d }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 4 offset_ps: 0 duration_ps: %(ms10)d }
  }
  lines { id: 3 name: "Steps" timestamp_ns: 1000000
    events { metadata_id: 5 offset_ps: 0 duration_ps: %(ms10)d }
  }
  event_metadata { key: 1 value { id: 1 name: "while.3" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_push(123)" } }
  event_metadata { key: 5 value { id: 5 name: "0" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: %(ms2)d duration_ps: %(ms2)d }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.2" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 11 name: "bench-worker-0" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0       duration_ps: %(ms10)d }
    events { metadata_id: 2 offset_ps: 0       duration_ps: %(ms4)d }
    events { metadata_id: 3 offset_ps: %(ms4)d duration_ps: %(ms1)d }
    events { metadata_id: 4 offset_ps: %(ms5)d duration_ps: %(ms4)d }
  }
  lines { id: 12 name: "bench-worker-1" timestamp_ns: 1000000
    events { metadata_id: 2 offset_ps: 0       duration_ps: %(ms1)d }
    events { metadata_id: 4 offset_ps: %(ms4)d duration_ps: %(ms6)d }
  }
  lines { id: 13 name: "other-thread" timestamp_ns: 1000000
    events { metadata_id: 5 offset_ps: 0 duration_ps: %(ms10)d }
  }
  lines { id: 14 name: "bench-tracer" timestamp_ns: 1000000
    events { metadata_id: 6 offset_ps: 0 duration_ps: %(ms10)d }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pull" } }
  event_metadata { key: 3 value { id: 3 name: "bench.grad" } }
  event_metadata { key: 4 value { id: 4 name: "bench.push" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(f)" } }
  event_metadata { key: 6 value { id: 6 name: "bench.traced_window" } }
}
""" % {
    "ms1": MS, "ms2": 2 * MS, "ms4": 4 * MS, "ms5": 5 * MS, "ms6": 6 * MS,
    "ms9": 9 * MS, "ms10": 10 * MS, "us50": MS // 20,
}

def write(path, text=TEXT):
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "small.xplane.pb")
    write(path)
    print(path, os.path.getsize(path))
