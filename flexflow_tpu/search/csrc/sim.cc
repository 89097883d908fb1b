// Native search core: event-driven task-graph simulator + MCMC annealer.
//
// The TPU re-design of the reference's C++ search engine
// (src/runtime/simulator.cc:93-621 TaskManager/SimTask per-device event
// simulation and src/runtime/model.cc:1652-1725 FFModel::optimize MCMC loop).
//
// Division of labor: Python (flexflow_tpu/search/cost_model.py) knows the
// machine model and computes COST TABLES —
//   * per op, per legal axis-map choice: compute seconds, gradient-sync comm
//     seconds (and the share of them that holds the compute stream: a
//     synchronous all-reduce), per-device memory bytes, devices spanned,
//   * per graph edge, per (producer choice, consumer choice) pair:
//     resharding comm seconds (GSPMD collectives within a device block), and
//     the seconds of the REDUCTIONS that edge causes (a CONTRACT producer's
//     psum by what the consumer keeps, a parameter-sharded consumer's input
//     gradient by what the producer keeps): synchronous on this backend, so
//     they hold the compute streams of both ends, and one tensor pays them
//     once however many consumers it has (the largest of its edges).
// This library evaluates a strategy — a (choice, placement) pair per op —
// with PER-DEVICE compute and comm timelines (reference
// simulator.cc:325-621): ops placed on disjoint device blocks overlap, ops
// sharing devices serialize, per-device HBM footprints accumulate and
// over-capacity is penalized at 1 ms/MB (reference simulator.cc:595-620),
// and a block-start mismatch between producer and consumer adds a p2p
// placement transfer (reference's inter-device task edges,
// simulator.cc:252-285). The MCMC proposes both axis-map choices and
// contiguous aligned device blocks (reference model.cc:496-525 random
// contiguous device ranges).
//
// Exposed via a C ABI for ctypes (no pybind11 in this environment).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <random>
#include <vector>

namespace {

struct Tables {
  int num_ops, num_edges, num_devices;
  const int64_t* op_cost_offsets;   // [num_ops+1]
  const double* op_compute_costs;   // per (op, choice)
  const double* op_sync_costs;      // per (op, choice)
  const double* op_exposed_costs;   // per (op, choice): the part of the sync
                                    // that also holds the compute stream
  const double* op_mem_bytes;       // per (op, choice): per-device HBM bytes
  const int32_t* op_ndev;           // per (op, choice): devices spanned
  const int32_t* edge_src;          // [num_edges], sorted by dst, src < dst
  const int32_t* edge_dst;
  const int64_t* edge_cost_offsets; // [num_edges+1]
  const double* edge_costs;         // row-major [src_choice][dst_choice]
  const double* edge_bytes;         // [num_edges]: full tensor bytes
  const double* edge_held_costs;    // like edge_costs: the edge's reductions,
                                    // which hold both ends' compute streams
  const int32_t* edge_tensor;       // [num_edges]: which tensor the edge
                                    // carries (ids < num_edges)
  double hbm_bytes, ici_bw, ici_latency, mem_penalty_per_byte;
};

struct Timeline {
  double *compute_start, *compute_finish;   // [num_ops] or null
  double *comm_start, *comm_finish;         // [num_edges] or null
  double *sync_start, *sync_finish;         // [num_ops] or null
};

// Clamp a desired block start so [place, place+ndev) fits and is aligned to
// the block grid (the GSPMD-expressible sub-meshes: ndev | num_devices and
// place a multiple of ndev; otherwise everything collapses to block 0).
int align_place(int place, int ndev, int num_devices) {
  if (ndev <= 0 || ndev >= num_devices || num_devices % ndev != 0) return 0;
  if (place < 0) place = 0;
  if (place > num_devices - ndev) place = num_devices - ndev;
  return place - place % ndev;
}

double schedule(const Tables& T, const int32_t* choices,
                const int32_t* places, const Timeline* tl) {
  const int D = T.num_devices;
  std::vector<double> finish(T.num_ops, 0.0);
  std::vector<double> dev_compute(D, 0.0);  // per-device compute stream
  std::vector<double> dev_comm(D, 0.0);     // per-device comm (ICI) stream
  // Gradient all-reduces ride a SEPARATE per-device stream: on TPU the
  // XLA latency-hiding scheduler overlaps grad sync with backward compute,
  // and the reference likewise prices NCCL cost post-hoc rather than
  // interleaving it with forward transfers (simulator.cc:548-594).
  // Interleaving syncs into dev_comm would stall every forward resharding
  // edge behind queued grad traffic and poison the search landscape.
  std::vector<double> dev_sync(D, 0.0);     // per-device grad-sync stream
  std::vector<double> dev_mem(D, 0.0);      // per-device HBM footprint
  std::vector<double> paid(T.num_edges + 1, 0.0);  // per tensor: reductions paid

  auto block = [&](int op) {
    int64_t off = T.op_cost_offsets[op];
    int n = T.op_ndev ? T.op_ndev[off + choices[op]] : D;
    if (n <= 0) n = 1;
    if (n > D) n = D;
    int p = places ? align_place(places[op], n, D) : 0;
    return std::pair<int, int>(p, n);
  };

  int e = 0;
  for (int i = 0; i < T.num_ops; ++i) {
    auto [pi, ni] = block(i);
    double ready = 0.0;
    // incoming comm (edges sorted by dst, topological)
    while (e < T.num_edges && T.edge_dst[e] == i) {
      int s = T.edge_src[e];
      auto [ps, ns] = block(s);
      int64_t off = T.edge_cost_offsets[e];
      int n_dst = (int)((T.edge_cost_offsets[e + 1] - off) /
                        (T.op_cost_offsets[s + 1] - T.op_cost_offsets[s]));
      double c = T.edge_costs[off + (int64_t)choices[s] * n_dst + choices[i]];
      if (T.edge_bytes && ps != pi) {
        // producer and consumer live on different device blocks: per-shard
        // p2p push over ICI (reference inter-device transfer tasks)
        c += T.edge_bytes[e] / std::max(ns, 1) / T.ici_bw + T.ici_latency;
      }
      double begin = finish[s], arrive = finish[s];
      if (c > 0.0) {
        // the transfer occupies the comm streams of both blocks
        double start = finish[s];
        for (int d = ps; d < ps + ns; ++d) start = std::max(start, dev_comm[d]);
        for (int d = pi; d < pi + ni; ++d) start = std::max(start, dev_comm[d]);
        double end = start + c;
        for (int d = ps; d < ps + ns; ++d) dev_comm[d] = end;
        for (int d = pi; d < pi + ni; ++d) dev_comm[d] = end;
        begin = start; arrive = end;
      }
      // the edge's reductions: what this tensor has not paid on an earlier
      // edge, run where nothing else computes on either block
      double h = 0.0;
      if (T.edge_held_costs) {
        int t = T.edge_tensor[e];
        h = T.edge_held_costs[off + (int64_t)choices[s] * n_dst + choices[i]]
            - paid[t];
        if (h > 0.0) paid[t] += h;
      }
      if (h > 0.0) {
        double start = arrive;
        for (int d = ps; d < ps + ns; ++d) start = std::max(start, dev_compute[d]);
        for (int d = pi; d < pi + ni; ++d) start = std::max(start, dev_compute[d]);
        double end = start + h;
        for (int d = ps; d < ps + ns; ++d) dev_compute[d] = end;
        for (int d = pi; d < pi + ni; ++d) dev_compute[d] = end;
        if (c <= 0.0) begin = start;
        arrive = end;
      }
      if (tl && tl->comm_start) { tl->comm_start[e] = begin; tl->comm_finish[e] = arrive; }
      ready = std::max(ready, arrive);
      ++e;
    }
    int64_t off = T.op_cost_offsets[i];
    double comp = T.op_compute_costs[off + choices[i]];
    double start = ready;
    for (int d = pi; d < pi + ni; ++d) start = std::max(start, dev_compute[d]);
    double end = start + comp;
    // what of the gradient's reduction this backend cannot hide (a
    // synchronous all-reduce) holds the compute stream after the op; the
    // sync stream below still carries the whole sync
    double held = end + T.op_exposed_costs[off + choices[i]];
    for (int d = pi; d < pi + ni; ++d) dev_compute[d] = held;
    finish[i] = end;
    if (tl && tl->compute_start) { tl->compute_start[i] = start; tl->compute_finish[i] = end; }
    // gradient sync rides this block's sync streams after the compute
    double sync = T.op_sync_costs[off + choices[i]];
    if (sync > 0.0) {
      double cstart = end;
      for (int d = pi; d < pi + ni; ++d) cstart = std::max(cstart, dev_sync[d]);
      double cend = cstart + sync;
      for (int d = pi; d < pi + ni; ++d) dev_sync[d] = cend;
      if (tl && tl->sync_start) { tl->sync_start[i] = cstart; tl->sync_finish[i] = cend; }
    } else if (tl && tl->sync_start) {
      tl->sync_start[i] = tl->sync_finish[i] = end;
    }
    if (T.op_mem_bytes) {
      double m = T.op_mem_bytes[off + choices[i]];
      for (int d = pi; d < pi + ni; ++d) dev_mem[d] += m;
    }
  }
  double total = 0.0;
  for (int d = 0; d < D; ++d)
    total = std::max(total, std::max(dev_sync[d],
                     std::max(dev_compute[d], dev_comm[d])));
  // per-device over-HBM penalty (reference simulator.cc:595-620: 1 ms/MB)
  if (T.op_mem_bytes && T.hbm_bytes > 0.0) {
    for (int d = 0; d < D; ++d) {
      double over = dev_mem[d] - T.hbm_bytes;
      if (over > 0.0) total += over * T.mem_penalty_per_byte;
    }
  }
  return total;
}

Tables make_tables(int num_ops, int num_edges, int num_devices,
                   const int64_t* op_cost_offsets,
                   const double* op_compute_costs,
                   const double* op_sync_costs,
                   const double* op_exposed_costs,
                   const double* op_mem_bytes,
                   const int32_t* op_ndev,
                   const int32_t* edge_src, const int32_t* edge_dst,
                   const int64_t* edge_cost_offsets,
                   const double* edge_costs,
                   const double* edge_bytes,
                   const double* edge_held_costs,
                   const int32_t* edge_tensor,
                   double hbm_bytes, double ici_bw, double ici_latency,
                   double mem_penalty_per_byte) {
  Tables T;
  T.num_ops = num_ops; T.num_edges = num_edges;
  T.num_devices = num_devices > 0 ? num_devices : 1;
  T.op_cost_offsets = op_cost_offsets;
  T.op_compute_costs = op_compute_costs;
  T.op_sync_costs = op_sync_costs;
  T.op_exposed_costs = op_exposed_costs;
  T.op_mem_bytes = op_mem_bytes;
  T.op_ndev = op_ndev;
  T.edge_src = edge_src; T.edge_dst = edge_dst;
  T.edge_cost_offsets = edge_cost_offsets;
  T.edge_costs = edge_costs;
  T.edge_bytes = edge_bytes;
  T.edge_held_costs = edge_held_costs;
  T.edge_tensor = edge_tensor;
  T.hbm_bytes = hbm_bytes;
  T.ici_bw = ici_bw > 0 ? ici_bw : 4.5e10;
  T.ici_latency = ici_latency;
  // 1 ms per MB over capacity when the caller passes 0 (reference
  // simulator.cc:612-617); cost_model.MEM_PENALTY_PER_BYTE feeds the real
  // value so the Python objective and this scheduler cannot drift
  T.mem_penalty_per_byte = mem_penalty_per_byte > 0.0 ? mem_penalty_per_byte
                                                      : 1e-3 / 1e6;
  return T;
}

}  // namespace

extern "C" {

double ff_simulate(int num_ops, int num_edges, int num_devices,
                   const int64_t* op_cost_offsets,
                   const double* op_compute_costs,
                   const double* op_sync_costs,
                   const double* op_exposed_costs,
                   const double* op_mem_bytes,
                   const int32_t* op_ndev,
                   const int32_t* edge_src, const int32_t* edge_dst,
                   const int64_t* edge_cost_offsets,
                   const double* edge_costs,
                   const double* edge_bytes,
                   const double* edge_held_costs,
                   const int32_t* edge_tensor,
                   const int32_t* choices, const int32_t* places,
                   double hbm_bytes, double ici_bw, double ici_latency,
                   double mem_penalty_per_byte) {
  Tables T = make_tables(num_ops, num_edges, num_devices, op_cost_offsets,
                         op_compute_costs, op_sync_costs, op_exposed_costs,
                         op_mem_bytes, op_ndev, edge_src, edge_dst, edge_cost_offsets,
                         edge_costs, edge_bytes, edge_held_costs, edge_tensor,
                         hbm_bytes, ici_bw,
                         ici_latency, mem_penalty_per_byte);
  return schedule(T, choices, places, nullptr);
}

double ff_simulate_timeline(int num_ops, int num_edges, int num_devices,
                            const int64_t* op_cost_offsets,
                            const double* op_compute_costs,
                            const double* op_sync_costs,
                            const double* op_exposed_costs,
                            const double* op_mem_bytes,
                            const int32_t* op_ndev,
                            const int32_t* edge_src, const int32_t* edge_dst,
                            const int64_t* edge_cost_offsets,
                            const double* edge_costs,
                            const double* edge_bytes,
                            const double* edge_held_costs,
                            const int32_t* edge_tensor,
                            const int32_t* choices, const int32_t* places,
                            double hbm_bytes, double ici_bw,
                            double ici_latency, double mem_penalty_per_byte,
                            double* compute_start, double* compute_finish,
                            double* comm_start, double* comm_finish,
                            double* sync_start, double* sync_finish) {
  Tables T = make_tables(num_ops, num_edges, num_devices, op_cost_offsets,
                         op_compute_costs, op_sync_costs, op_exposed_costs,
                         op_mem_bytes, op_ndev, edge_src, edge_dst, edge_cost_offsets,
                         edge_costs, edge_bytes, edge_held_costs, edge_tensor,
                         hbm_bytes, ici_bw,
                         ici_latency, mem_penalty_per_byte);
  Timeline tl{compute_start, compute_finish, comm_start, comm_finish,
              sync_start, sync_finish};
  return schedule(T, choices, places, &tl);
}

// MCMC simulated annealing (reference: model.cc:1663-1725). A proposal is
// one of (mirror of the Python annealer in search/driver.py):
//   * one op: re-randomize its axis-map choice or its device block
//     (reference rewrite model.cc:1652-1661 + random contiguous ranges
//     model.cc:496-525) -- one proposal in four;
//   * one TIED GROUP (ops that play the same part in a repeated block:
//     driver.tied_groups; every member holds the same choice list): all
//     members take one new choice -- the rest, and in two of those three
//     every FOLLOWER downstream (an op with no parameter dim and one
//     producer shape: follow_src) takes the choice that equals what its
//     producer now delivers (follow_tbl), in topological order, so a chain
//     of elementwise ops moves with the matmul that feeds it.
// Returns the best cost; best_choices/best_places filled with the best
// strategy.
double ff_mcmc(int num_ops, int num_edges, int num_devices,
               const int64_t* op_cost_offsets,
               const double* op_compute_costs,
               const double* op_sync_costs,
               const double* op_exposed_costs,
               const double* op_mem_bytes,
               const int32_t* op_ndev,
               const int32_t* edge_src, const int32_t* edge_dst,
               const int64_t* edge_cost_offsets,
               const double* edge_costs,
               const double* edge_bytes,
               const double* edge_held_costs,
               const int32_t* edge_tensor,
               const int32_t* init_choices, const int32_t* init_places,
               double hbm_bytes, double ici_bw, double ici_latency,
               double mem_penalty_per_byte,
               int allow_place,  // 0: never propose device-block moves
                                 // (FSDP shards weights over the FULL
                                 // mesh, incompatible with sub-meshes)
               int num_groups,
               const int32_t* group_offsets,   // [num_groups+1]
               const int32_t* group_members,   // op indices, by group
               const int32_t* follow_src,      // [num_ops]: producer or -1
               const int64_t* follow_offsets,  // [num_ops+1] into follow_tbl
               const int32_t* follow_tbl,      // per producer choice: own
                                               // choice, or -1 (not legal)
               int budget, double alpha, uint64_t seed,
               int32_t* best_choices, int32_t* best_places) {
  Tables T = make_tables(num_ops, num_edges, num_devices, op_cost_offsets,
                         op_compute_costs, op_sync_costs, op_exposed_costs,
                         op_mem_bytes, op_ndev, edge_src, edge_dst, edge_cost_offsets,
                         edge_costs, edge_bytes, edge_held_costs, edge_tensor,
                         hbm_bytes, ici_bw,
                         ici_latency, mem_penalty_per_byte);
  const int D = T.num_devices;
  std::mt19937_64 rng(seed);
  std::uniform_real_distribution<double> unif(0.0, 1.0);

  std::vector<int32_t> cur_c(init_choices, init_choices + num_ops);
  std::vector<int32_t> cur_p(num_ops, 0);
  if (init_places) cur_p.assign(init_places, init_places + num_ops);

  auto ndev_of = [&](int op, int choice) {
    int n = op_ndev ? op_ndev[op_cost_offsets[op] + choice] : D;
    return std::max(1, std::min(n, D));
  };
  auto eval = [&]() { return schedule(T, cur_c.data(), cur_p.data(), nullptr); };
  auto set_choice = [&](int op, int c) {
    cur_c[op] = c;
    cur_p[op] = align_place(cur_p[op], ndev_of(op, c), D);
  };
  std::vector<char> moved(num_ops, 0);

  double cur_cost = eval();
  std::vector<int32_t> best_c = cur_c, best_p = cur_p;
  double best_cost = cur_cost;

  int reset_span = budget / 100;
  if (reset_span < 1) reset_span = 1;
  if (reset_span > 1000) reset_span = 1000;  // reference model.cc:1673-1677

  for (int it = 0; it < budget; ++it) {
    if (it > 0 && it % reset_span == 0) {
      cur_c = best_c; cur_p = best_p;
      cur_cost = best_cost;
    }
    const std::vector<int32_t> old_c = cur_c, old_p = cur_p;
    if (rng() % 4 == 0) {
      int op = (int)(rng() % (uint64_t)num_ops);
      int n_choices = (int)(op_cost_offsets[op + 1] - op_cost_offsets[op]);
      // half the proposals move the device block, half the axis map
      // (reference re-randomizes both at once; splitting mixes faster)
      bool move_place = allow_place && (rng() & 1) != 0;
      int ndev = ndev_of(op, cur_c[op]);
      int nblocks = (ndev < D && D % ndev == 0) ? D / ndev : 1;
      if (move_place && nblocks > 1) {
        cur_p[op] = (int)(rng() % (uint64_t)nblocks) * ndev;
      } else {
        if (n_choices <= 1) continue;
        set_choice(op, (int)(rng() % (uint64_t)n_choices));
      }
    } else {
      int g = (int)(rng() % (uint64_t)num_groups);
      bool follow = rng() % 3 != 0;
      int first = group_members[group_offsets[g]];
      int n_choices = (int)(op_cost_offsets[first + 1] - op_cost_offsets[first]);
      int new_c = (int)(rng() % (uint64_t)n_choices);
      std::fill(moved.begin(), moved.end(), 0);
      for (int k = group_offsets[g]; k < group_offsets[g + 1]; ++k) {
        set_choice(group_members[k], new_c);
        moved[group_members[k]] = 1;
      }
      if (follow) {
        for (int i = 0; i < num_ops; ++i) {
          int src = follow_src[i];
          if (src < 0 || !moved[src] || moved[i]) continue;
          int c = follow_tbl[follow_offsets[i] + cur_c[src]];
          if (c < 0) continue;
          set_choice(i, c);
          moved[i] = 1;
        }
      }
    }
    if (cur_c == old_c && cur_p == old_p) continue;
    double new_cost = eval();
    double diff = new_cost - cur_cost;
    // reference accepts with prob exp(-alpha*diff) on simulated ms; our
    // costs are seconds, so scale to ms for comparable alpha semantics
    if (diff < 0.0 || unif(rng) < std::exp(-alpha * diff * 1e3)) {
      cur_cost = new_cost;
      if (new_cost < best_cost) {
        best_cost = new_cost;
        best_c = cur_c; best_p = cur_p;
      }
    } else {
      cur_c = old_c; cur_p = old_p;
    }
  }
  std::memcpy(best_choices, best_c.data(), sizeof(int32_t) * num_ops);
  if (best_places) std::memcpy(best_places, best_p.data(),
                               sizeof(int32_t) * num_ops);
  return best_cost;
}

}  // extern "C"
