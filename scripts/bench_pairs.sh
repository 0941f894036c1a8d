#!/usr/bin/env bash
# One cell of the benchmark from several checkouts in ONE chip call, so that
# the sides of a comparison are measured on the same chip:
#
#   chiprun --timeout 1500 -- bash scripts/bench_pairs.sh OUT CELL RUN [RUN ...]
#
# OUT is a directory under chiprun_out/; a RUN is dir:seed:trace:tag (dir a
# checkout under the repo's root, as _parent/ from `git archive <parent>` and
# _checkout/ from `git archive $(git write-tree)`; the two sides of one
# comparison share a seed).  PR 50's last runs, for one:
#
#   bash scripts/bench_pairs.sh chiprun_out/pr50/last/joyai joyaiflash-ws1-seq16k \
#       _parent:3000050901:0:1-P _checkout:3000050901:0:2-C _checkout:3000050902:1:3-Ct
#
# Each run's stdout goes to OUT/tag.out (its last line is the result), its
# stderr to OUT/tag.err, its exit code to OUT/exits.txt; the head of every
# result line is echoed.
set -u
root=$(pwd); out=$root/$1; cell=$2; shift 2
mkdir -p "$out"
for run in "$@"; do
  IFS=: read -r dir seed trace tag <<<"$run"
  ( cd "$root/$dir" && PYTHONFAULTHANDLER=1 python3 ftbench/run.py --workload "$cell" --seed "$seed" \
      --seconds "$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')" \
      --trace "$trace" > "$out/$tag.out" 2> "$out/$tag.err"; echo "$tag $dir seed $seed trace $trace exit $?" >> "$out/exits.txt" )
  tail -n 1 "$out/$tag.out" | cut -c 1-700
done
cat "$out/exits.txt"
