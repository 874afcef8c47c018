// FastDFS-style INI reader (reference: libfastcommon ini_file_reader.c).
// Same syntax as fastdfs_tpu/common/ini_config.py: flat key=value, '#'
// comments, repeated keys, '#include file' relative to the including file.
#pragma once

#include <cstdint>
#include <map>
#include <optional>
#include <string>
#include <vector>

namespace fdfs {

class IniConfig {
 public:
  // Returns false and fills *error on IO error / include cycle.
  bool LoadFile(const std::string& path, std::string* error);
  bool LoadString(const std::string& text, std::string* error);

  std::optional<std::string> Get(const std::string& key) const;
  std::vector<std::string> GetAll(const std::string& key) const;
  std::string GetStr(const std::string& key, const std::string& dflt) const;
  int64_t GetInt(const std::string& key, int64_t dflt) const;
  bool GetBool(const std::string& key, bool dflt) const;
  // Sizes with K/M/G/T suffixes (e.g. "256KB", "64MB").
  int64_t GetBytes(const std::string& key, int64_t dflt) const;
  // The same for a value that is not a key's whole text (one field of
  // dedup_cdc_widths); dflt on an unknown suffix.
  static int64_t ParseBytes(const std::string& text, int64_t dflt);
  // Durations with s/m/h/d suffixes.
  int64_t GetSeconds(const std::string& key, int64_t dflt) const;
  bool Has(const std::string& key) const { return items_.count(key) > 0; }

 private:
  bool ParseLines(const std::string& text, const std::string& base_dir,
                  std::vector<std::string>* stack, std::string* error);
  bool LoadFileInner(const std::string& path, std::vector<std::string>* stack,
                     std::string* error);
  std::map<std::string, std::vector<std::string>> items_;
};

}  // namespace fdfs
