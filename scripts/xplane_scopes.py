"""Device self time by innermost ``moose/<scope>``, from a raw ``.xplane.pb``:
``python scripts/xplane_scopes.py <file.xplane.pb> <evaluations in the trace>``.

By hand, for PERF.md section 5's tables: the scope of a device op is in
the ``tf_op`` stat of its event's *metadata*, which
``jax.profiler.ProfileData`` does not show, so this reads the proto with
TensorFlow's ``xplane_pb2`` (installed here).  Never import it in the
process that holds the chip: bring the file back, or run this after the
traced run has ended.  Prints milliseconds per evaluation by innermost scope, the
four largest instructions of each, the 25 largest scope paths, and
(since PR 35, for programs whose scopes nest: `moose/softmax` holds
`moose/max` and `moose/exp`) the time by outermost scope with the scope
just inside it.  A ``.gz`` file is read through gzip.
"""
import sys, re, collections, gzip
from tensorflow.tsl.profiler.protobuf import xplane_pb2
path, n_evals = sys.argv[1], int(sys.argv[2])
opener = gzip.open if path.endswith('.gz') else open
space = xplane_pb2.XSpace(); space.ParseFromString(opener(path, 'rb').read())
for plane in space.planes:
    if not plane.name.startswith('/device:TPU:0'):
        continue
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    for line in plane.lines:
        if line.name != 'XLA Ops':
            continue
        evs = sorted(line.events, key=lambda e: (e.offset_ps, -e.duration_ps))
        # self time: subtract children nested inside
        self_ps = [e.duration_ps for e in evs]
        stack = []
        for i, e in enumerate(evs):
            while stack and evs[stack[-1]].offset_ps + evs[stack[-1]].duration_ps <= e.offset_ps:
                stack.pop()
            if stack:
                self_ps[stack[-1]] -= e.duration_ps
            stack.append(i)
        by_scope = collections.Counter(); by_scope_op = collections.defaultdict(collections.Counter)
        by_path = collections.Counter()
        by_outer = collections.defaultdict(collections.Counter)
        for e, sp in zip(evs, self_ps):
            md = plane.event_metadata[e.metadata_id]
            tf_op = ''
            for st in md.stats:
                if stat_names.get(st.metadata_id) == 'tf_op':
                    tf_op = st.str_value or stat_names.get(st.ref_value, '')
            scopes = re.findall(r'moose/([A-Za-z0-9_]+)', tf_op)
            inner = scopes[-1] if scopes else '(none)'
            by_scope[inner] += sp
            by_path['/'.join(scopes) or '(none)'] += sp
            by_outer[scopes[0] if scopes else '(none)'][scopes[1] if len(scopes) > 1 else '(itself)'] += sp
            name = re.sub(r'[.\d]+$', '', md.name.split(' = ')[0].lstrip('%'))
            by_scope_op[inner][name] += sp
        total = sum(by_scope.values())
        print(f'total self ms/eval {total/1e9/n_evals:.2f} over {len(evs)} events')
        for s, ps in by_scope.most_common():
            tops = ', '.join(f'{n} {p/1e9/n_evals:.2f}' for n, p in by_scope_op[s].most_common(4))
            print(f'{s:16s} {ps/1e9/n_evals:8.2f} ms  {100*ps/total:5.1f}%   {tops}')
        print('--- by scope path (top 25)')
        for s, ps in by_path.most_common(25):
            print(f'{ps/1e9/n_evals:8.2f} ms  {s}')
        print('--- by outermost scope, and the scope just inside it')
        for s, inside in sorted(by_outer.items(), key=lambda kv: -sum(kv[1].values())):
            ps = sum(inside.values())
            parts = ', '.join(f'{n} {p/1e9/n_evals:.2f}' for n, p in inside.most_common(8))
            print(f'{s:16s} {ps/1e9/n_evals:8.2f} ms  {100*ps/total:5.1f}%   {parts}')
