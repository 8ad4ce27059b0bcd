"""From a profiler trace (`.xplane.pb`, read with jax.profiler.ProfileData)
to numbers: how long each device was busy inside the traced window, which
operations took that time, what the collectives cost and how much of it no
compute hid, and what the host was doing at the start of each gap in which
a device sat idle.

What a TPU v5e trace holds, from reading three by hand (PERF.md section 3):

- one plane per chip, `/device:TPU:<n>`, with the lines `Steps`, `XLA
  Modules` (one event per executed program), `XLA Ops` and `Async XLA Ops`.
  An event of `XLA Ops` is one executed HLO instruction and is named by the
  instruction's whole text: `%copy.964 = bf16[36,256,64,20,64]{...}
  copy(...)`. A `while` is an event that contains its body's events. A
  Mosaic (Pallas) kernel is a `custom-call` whose text says
  `custom_call_target="tpu_custom_call"`, under a name taken from the
  jitted function around it (`%unified.36`, `%transpose_jvp___.12`); other
  custom calls (`ConcatBitcast`, `AllocateBuffer`) are the compiler's own.
  `Async XLA Ops` holds, for each `-start` instruction, one event that
  lasts until its `-done`. Events carry no category.
- `/host:CPU` has one line per thread, named by the thread; the Python
  thread's line holds each `jax.profiler.TraceAnnotation` under its own
  name (the benchmark's are `bench.*`, the program's `serving.*`) among
  the profiler's own `$file:line function` events. Both planes count
  nanoseconds from the start of the trace on one clock.
- the planes `#Chip0 ...`, `/host:metadata`, `/device:CUSTOM:Megascale
  Trace` and `Task Environment` are empty.

Times are returned in seconds.
"""
import collections
import functools
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HOST_PLANE = "/host:CPU"
# spans the benchmark or the program put there: dotted lower-case names
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
WINDOW_SPAN = "bench.traced"
# instructions that only hold others: their time is their children's
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


MOSAIC = "mosaic"
_HEAD = re.compile(r"^%(\S+) = ")


# a trace repeats each instruction once per step: parse each text once
@functools.lru_cache(maxsize=1 << 16)
def parse(text):
    """(label, kind) of an event of `XLA Ops`. For an instruction's text
    the kind is its opcode, except that a custom call is known by its
    target and a Mosaic kernel as `mosaic`; the label is the instruction's
    name without its number, its kind and its result shape without the
    layout (of a tuple, the first), so that the twelve layers' copies of
    one instruction share a label. Any other text (a trace made by hand)
    is its own label, and its kind is the text up to a trailing number."""
    m = _HEAD.match(text)
    if not m:
        return text, re.sub(r"[.:]\d+$", "", text)
    rest = text[m.end():]
    if rest.startswith("("):            # a tuple shape: to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape = f"({rest[1:i].split('{')[0]}, ...)"
        rest = rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    kind = rest.split("(", 1)[0]
    if kind == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        kind = target.group(1) if target else kind
        kind = MOSAIC if kind == "tpu_custom_call" else kind
    base = re.sub(r"\.\d+$", "", m.group(1))
    return f"{base} {kind} {shape}", kind


def kind_of(text):
    return parse(text)[1]


def is_collective(text):
    return kind_of(text).startswith(COLLECTIVES)


def in_flight(ops):
    """The intervals in which a collective was under way on one chip, from
    its (start, end, kind) events. A
    synchronous one is its own event. An asynchronous one is a `-start` and
    a `-done` event with other instructions between them: each `-done` is
    paired with the oldest unpaired `-start` of its kind, and the
    collective is under way from the start's beginning to the done's end."""
    out, waiting = [], collections.defaultdict(collections.deque)
    for a, b, kind in sorted(ops):
        if not kind.startswith(COLLECTIVES):
            continue
        if kind.endswith("-start"):
            waiting[kind[:-len("-start")]].append(a)
        elif kind.endswith("-done"):
            pending = waiting[kind[:-len("-done")]]
            out.append((pending.popleft() if pending else a, b))
        else:
            out.append((a, b))
    return out


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of `intervals` that no interval of `holes` covers (both
    sorted and disjoint)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > a:
                out.append([a, holes[k][0]])
            a = max(a, holes[k][1])
            k += 1
        if a < b:
            out.append([a, b])
    return out


def clip(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def self_times(events):
    """Seconds by name, each event counted for the part of it that no event
    nested inside it covers, so that a `while` does not count its body
    twice. `events` are (start, end, name) of ONE timeline."""
    out = collections.Counter()
    stack = []          # [end, name, start, seconds its children cover]

    def close():
        end, name, start, covered = stack.pop()
        out[name] += (end - start) - covered
        if stack:
            stack[-1][3] += end - start

    for a, b, n in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            close()
        if stack:
            b = min(b, stack[-1][0])
        stack.append([b, n, a, 0.0])
    while stack:
        close()
    return out


def _events(line):
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def host_spans(space):
    """Every benchmark or program span of the host plane, all threads."""
    spans = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans.extend(e for e in _events(line) if SPAN_NAME.match(e[2]))
    return sorted(spans)


def span_open_at(spans, t):
    """The innermost (latest-started) span open at time t, or None."""
    best = None
    for a, b, n in spans:
        if a > t:
            break
        if b > t and (best is None or a >= best[0]):
            best = (a, b, n)
    return best[2] if best else None


def reduce(space):
    """The whole reduction. None where the trace has no device plane (a CPU
    run): there is then nothing to say about a device."""
    devices = {}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        if not lines.get(OPS_LINE):
            raise ValueError(
                f"{plane.name} has no events on a line named {OPS_LINE!r}; "
                f"its lines are {sorted(lines)}")
        devices[int(m.group(1))] = (lines[OPS_LINE],
                                    lines.get(ASYNC_LINE, []))
    if not devices:
        return None
    spans = host_spans(space)
    window = next(((a, b) for a, b, n in spans if n == WINDOW_SPAN), None)
    if window is None:
        window = (min(e[0] for ops, _ in devices.values() for e in ops),
                  max(e[1] for ops, _ in devices.values() for e in ops))
    lo, hi = window
    spans = clip(spans, lo, hi)
    per_device, by_op, gaps = [], collections.Counter(), collections.Counter()
    kinds = {}
    for _, (ops, async_ops) in sorted(devices.items()):
        named = []
        for a, b, text in clip(ops, lo, hi):
            label, kind = parse(text)
            if kind not in CONTAINERS:
                kinds[label] = kind
                named.append((a, b, label))
        ops = named
        busy = union([(a, b) for a, b, _ in ops])
        compute = union([(a, b) for a, b, n in ops
                         if not kinds[n].startswith(COLLECTIVES)])
        # a collective is under way from its start to its done: paired on
        # the instruction line, and as one event on the asynchronous line
        coll = union(in_flight([(a, b, kinds[n]) for a, b, n in ops]) + [
            (a, b) for a, b, text in clip(async_ops, lo, hi)
            if is_collective(text)])
        per_device.append({
            "busy_s": total(busy),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, compute)),
        })
        for name, s in self_times(ops).items():
            by_op[name] += s / len(devices)
        for a, b in subtract([[lo, hi]], busy):
            gaps[span_open_at(spans, a) or "(no span)"] += \
                (b - a) / len(devices)
    n = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n
    by_kind = collections.Counter()
    for name, s in by_op.items():
        by_kind[kinds[name]] += s
    return {
        "window_s": hi - lo,
        "chips": n,
        "busy_s": mean("busy_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "per_device": per_device,
        "by_op": dict(by_op),
        "by_kind": dict(by_kind),
        "idle_gaps": dict(gaps),
        "spans": collections.Counter(n for _, _, n in spans),
    }


def top(table, n=10):
    """[[name, seconds], ...], the n largest."""
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)
